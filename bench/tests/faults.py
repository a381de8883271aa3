"""Faults planted under the timed path, for the harness's own tests.

``plant(name)`` breaks the program in this process before a run:

- ``token_altered``: the serving engine's third token of every request is
  replaced by the next vocabulary id where it is produced.
"""

from __future__ import annotations


def _token_altered():
    from repro.serve.engine import ServeEngine

    step = ServeEngine.step

    def altered(self):
        out = step(self)
        for req in self.requests.values():
            if len(req.generated) == 3 and not getattr(req, "_altered", False):
                req.generated[-1] = (req.generated[-1] + 1) % self.cfg.vocab_size
                req._altered = True
        return out

    ServeEngine.step = altered


FAULTS = {"token_altered": _token_altered}


def plant(name: str) -> None:
    FAULTS[name]()
