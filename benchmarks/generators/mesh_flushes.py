"""``mesh_flushes``: ``flushes`` over a backend that shards every chunk of a
flush across the cell's chips (``SIG_MESH`` in the configuration's ``node``).

The traffic, the timed path and the comparison with libsodium are
``flushes.Workload``'s, unchanged: the 5,000-wide adversarial batch rides a
4,096-lane chunk (every shard full) and a 2,048-lane tail chunk with a full
shard, a partly filled one and two dead ones, so every kind of bad lane
falls in every shard.  What this generator adds is the part of the
configuration's ``sharding`` guarantee a run can show: the backend really
ran over as many devices as the cell has chips — on a host where ``"auto"``
quietly gave an unsharded backend the verdicts would still be right and the
rate would be one chip's — and each of them was handed live lanes.
"""

from __future__ import annotations

from benchmarks.generators import flushes


class Workload(flushes.Workload):
    def check(self, check) -> tuple:
        attempted, failed = super().check(check)
        stats = self.backend.stats()
        chips = int(self.ctx.cell["chips"])
        check.compare("mesh_devices_off", abs(chips - stats["mesh_devices"]), 0, f"the cell has {chips} chips")
        # a program without the block (the parent) has no count to hold
        mesh = stats.get("mesh")
        if mesh is not None:
            idle = sum(1 for lanes in mesh["lanes_per_device"] if lanes == 0)
            check.compare("mesh_devices_without_a_live_lane", idle, 0, f"of {mesh['devices']}")
        traced = stats["first_dispatch"].get("programs_traced")
        print(f"programs_traced: {traced} (reported, not compared)", flush=True)
        return attempted, failed
