"""Launch layer: meshes, sharding rules, step builders, drivers."""

from repro.launch import mesh, sharding  # noqa: F401
