"""signature backend (crypto/sigbackend.py): the chips the backend's mesh
shards a flush over (``mesh_devices`` of the ``sig_backend`` counters as the
window closed; 0 = unsharded).  The engagement reader of the four-chip cell:
where this reads under the cell's chips, every other number of the line is
some smaller deployment's."""


def read(run):
    return run["counters"]["after"]["sig_backend"].get("mesh_devices")
