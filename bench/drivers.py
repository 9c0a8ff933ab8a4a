"""The calls a cell makes, through the program's own entry points.

A traffic mix names its driver:

* ``replay`` — ``simulate_stream`` over consecutive segments of one
  request stream, each segment from an empty cache (a user replaying a
  trace through a policy);
* ``sweep`` — ``sweep_grid`` over consecutive segments of a trace, one
  call per segment over every (policy, omega, capacity) lane (a user
  comparing policies or sweeping hyperparameters).

Any other name is the file ``bench/callers/<name>.py`` and its class
``Driver``, a subclass of :class:`Segments` (``bench/cell.py``).

The benchmark makes every request itself (``bench/generate.py``, the
generator the configuration names); the program receives the arrays as a
``RequestStream`` or through ``make_trace``, with the benchmark's fetch
draws.  Each call's answer is pulled to the host as numpy, one value per
lane, and :meth:`jobs` says how the plain reference recomputes a
segment's lanes; a driver's ``fields`` are the answer fields pulled and
compared, and its ``reference`` names the plain reference that
recomputes them.  A traffic mix also names the end-to-end metric its
calls' work rate is reported under (``rate_metric``).
"""
from __future__ import annotations

import numpy as np

from bench import generate as G
from bench.cell import BUILTIN_REFERENCE, load_plugin

FIELDS = ("total_latency", "n_hits", "n_delayed", "n_misses", "n_evictions")


def _draws(unit):
    """A fetch-latency law whose unit draws are the benchmark's own."""
    from repro.core.distributions import MissLatency

    class BenchDraws(MissLatency):
        name = "bench_draws"

        def sample_unit(self, key, shape):
            if tuple(shape) != unit.shape:
                raise ValueError(f"draws for {shape}, have {unit.shape}")
            return unit

    return BenchDraws()


def _pull(result, fields) -> dict:
    import jax
    host = jax.device_get([getattr(result, f) for f in fields])
    return {f: np.asarray(v, np.float64).reshape(-1)
            for f, v in zip(fields, host)}


class Segments:
    """What every driver shares: consecutive segments that divide the
    configuration's trace, and set-up in two timed parts, the benchmark's
    own generation (numpy) and the program's ingest of it."""

    fields = FIELDS
    reference = BUILTIN_REFERENCE

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg, self.tr, self.seed = config, traffic, seed
        self.seg_len = int(traffic["segment_requests"])
        n = int(config["n_requests"])
        if self.seg_len < 1 or n % self.seg_len:
            raise ValueError(f"segment_requests={self.seg_len} must divide "
                             f"the trace's {n} requests")
        self.n_segments = n // self.seg_len
        self.rate_metric = traffic["rate_metric"]

    def prepare(self, span) -> None:
        with span("generate"):
            self.generate()
        with span("ingest"):
            self.ingest()

    def generate(self) -> None:
        self.ref_in = G.requests(self.cfg, self.tr, self.seed)
        self.coin_seed = G.coin_seed(self.seed)

    def capacity(self, scale: float) -> float:
        """The cache size (MB) of a lane: the deployment's, times the
        traffic mix's scale."""
        return float(np.float32(float(scale)
                                * float(self.cfg["capacity_mb"])))

    def _job(self, **lane) -> dict:
        """A reference job in the state precision the configuration
        states."""
        return dict(lane, dtype=self.cfg["precision"]["state"],
                    estimate_z=bool(self.tr["estimate_z"]),
                    coin_seed=self.coin_seed)

    def _slice(self, segment: int) -> slice:
        return slice(segment * self.seg_len, (segment + 1) * self.seg_len)

    @property
    def n_objects(self) -> int:
        return len(self.ref_in["sizes"])


class Replay(Segments):
    """``simulate_stream`` over consecutive segments of a stream."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        if self.seg_len % int(traffic["chunk_size"]):
            raise ValueError("segment_requests must be a multiple of "
                             "chunk_size")
        self.lanes = [(traffic["policy"], float(traffic["omega"]),
                       self.capacity(traffic["capacity_scale"]))]
        self.work_per_call = self.seg_len

    def ingest(self) -> None:
        import jax

        from repro.core import PolicyParams
        from repro.core.trace import RequestStream
        s = self.ref_in
        self.segments = [RequestStream(
            times=s["times"][sl], objs=s["objs"][sl], sizes=s["sizes"],
            z_mean=s["z_mean"], z_draw=s["z_draw"][sl])
            for sl in map(self._slice, range(self.n_segments))]
        self.params = PolicyParams(omega=self.lanes[0][1])
        self.key = jax.random.key(self.coin_seed)

    def release(self) -> None:
        self.segments = self.key = None

    def _replay(self, stream) -> dict:
        from repro.core import simulate_stream
        t = self.tr
        return _pull(simulate_stream(
            stream, self.lanes[0][2], t["policy"], self.params, key=self.key,
            estimate_z=bool(t["estimate_z"]), use_kernel=t["use_kernel"],
            chunk_size=int(t["chunk_size"]),
            state_mode=t.get("state_mode", "dense")), self.fields)

    def warm(self) -> None:
        """One whole chunk compiles (or loads) the one program every
        segment runs: segments are whole numbers of chunks."""
        s, c = self.segments[0], int(self.tr["chunk_size"])
        self._replay(s._replace(times=s.times[:c], objs=s.objs[:c],
                                z_draw=s.z_draw[:c]))

    def call(self, k: int) -> dict:
        return self._replay(self.segments[k % self.n_segments])

    def jobs(self, segment: int, control: dict | None = None) -> list:
        s, sl = self.ref_in, self._slice(segment)
        pol, omega, cap = self.lanes[0]
        job = self._job(times=s["times"][sl], objs=s["objs"][sl],
                        z_draw=s["z_draw"][sl], sizes=s["sizes"],
                        z_mean=s["z_mean"], capacity=cap, policy=pol,
                        omega=omega, chunk=int(self.tr["chunk_size"]))
        return [dict(job, **(control or {}))]


class Sweep(Segments):
    """``sweep_grid`` over consecutive segments of a resident trace."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        self.lanes = [(p, float(w), float(c))
                      for p in traffic["policies"]
                      for w in traffic["omegas"]
                      for c in map(self.capacity,
                                   traffic["capacity_scales"])]
        self.work_per_call = len(self.lanes) * self.seg_len

    def generate(self) -> None:
        super().generate()
        # the in-memory trace's clock is float32: both sides read these
        self.times32 = self.ref_in["times"].astype(np.float32)

    def ingest(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.core import PolicyParams
        from repro.core.trace import make_trace
        b = self.ref_in
        self.traces = [make_trace(
            self.times32[sl], b["objs"][sl], b["sizes"], b["z_mean"],
            key=jax.random.key(0), dist=_draws(b["unit"][sl]))
            for sl in map(self._slice, range(self.n_segments))]
        jax.block_until_ready(self.traces)
        self.params = [PolicyParams(omega=float(w))
                       for w in self.tr["omegas"]]
        self.caps_dev = jnp.asarray(
            [self.capacity(c) for c in self.tr["capacity_scales"]],
            jnp.float32)
        pols = list(self.tr["policies"])
        self.policies = pols[0] if len(pols) == 1 else pols
        d = int(self.tr.get("devices", 1))
        self.devices = d if d > 1 else None

    def release(self) -> None:
        self.traces = self.caps_dev = None

    def warm(self) -> None:
        self.call(0)

    def call(self, k: int) -> dict:
        from repro.core import sweep_grid
        r = sweep_grid(self.traces[k % self.n_segments], self.caps_dev,
                       self.policies, self.params, seeds=(self.coin_seed,),
                       estimate_z=bool(self.tr["estimate_z"]),
                       devices=self.devices).result
        return _pull(r, self.fields)

    def jobs(self, segment: int, control: dict | None = None) -> list:
        b, sl = self.ref_in, self._slice(segment)
        return [dict(self._job(times=self.times32[sl], objs=b["objs"][sl],
                               z_draw=b["z_draw"][sl], sizes=b["sizes"],
                               z_mean=b["z_mean"], capacity=c, policy=p,
                               omega=w, chunk=None), **(control or {}))
                for p, w, c in self.lanes]


DRIVERS = {"replay": Replay, "sweep": Sweep}


def build(config: dict, traffic: dict, seed: int):
    """The driver a traffic mix names: a built-in, else the ``Driver`` of
    ``bench/callers/<name>.py``."""
    kind = traffic["driver"]
    cls = DRIVERS[kind] if kind in DRIVERS else load_plugin(
        "driver", kind, DRIVERS).Driver
    return cls(config, traffic, seed)
