"""The program's model configuration, built from the configuration file."""

from __future__ import annotations


def program_config(cell):
    """(registry config, config as the file states it) for the cell's
    architecture; the file's keys map onto the program's fields through its
    ``program.fields`` group."""
    from repro import configs

    prog = cell.config["program"]
    base = configs.get_smoke(prog["arch"]) if cell.tiny \
        else configs.get(prog["arch"])
    want = {field: cell.config[key] for field, key in prog["fields"].items()}
    return base, base.replace(**want)

