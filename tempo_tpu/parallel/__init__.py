from .mesh import make_mesh, scan_mesh_axes

__all__ = ["make_mesh", "scan_mesh_axes"]
