"""``simulate_stream(state_mode="slots")`` over consecutive segments of a
stream, with the deployment's slot table.

As the built-in ``replay`` driver, with three differences: every call
passes the configuration's ``n_slots``, so the table is the deployment's
and not the one a segment would size; the answer carries the table's
``n_inserts`` and ``n_reclaims``; and the plain reference is
``slot_exact``, which replays each segment over its own keys.
``n_objects`` is the slot count, the length of the per-object columns the
program keeps on the device and scores.
"""
from __future__ import annotations

import numpy as np

from bench.drivers import FIELDS, Replay, _pull

# the load above which the deployment's table no longer holds a segment's
# keys as its exact replay assumes (the configuration's ``assumed.table``)
MAX_LOAD = 0.75


class Driver(Replay):
    fields = FIELDS + ("n_inserts", "n_reclaims")
    reference = "slot_exact"

    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        self.n_slots = int(config["n_slots"])

    def generate(self) -> None:
        super().generate()
        objs = self.ref_in["objs"]
        most = max(np.unique(objs[self._slice(k)]).size
                   for k in range(self.n_segments))
        if most > MAX_LOAD * self.n_slots:
            raise ValueError(f"a segment touches {most} keys: more than "
                             f"{MAX_LOAD} of the {self.n_slots}-slot table")

    def ingest(self) -> None:
        # a program whose slot replay reports no table counters cannot run
        # this cell: fail here, before any compile
        from repro.core import SlotResult  # noqa: F401
        super().ingest()

    @property
    def n_objects(self) -> int:
        return self.n_slots

    def _replay(self, stream) -> dict:
        from repro.core import simulate_stream
        t = self.tr
        return _pull(simulate_stream(
            stream, self.lanes[0][2], t["policy"], self.params, key=self.key,
            estimate_z=bool(t["estimate_z"]), use_kernel=t["use_kernel"],
            chunk_size=int(t["chunk_size"]), state_mode=t["state_mode"],
            n_slots=self.n_slots), self.fields)
