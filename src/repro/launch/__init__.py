"""Launchers: the mesh constructor, train/serve drivers, OAR cluster runner."""
