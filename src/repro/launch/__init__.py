"""Launchers: meshes, train/serve/quantize entry points."""
