"""Core library: the paper's delayed-hit caching technique.

- :mod:`delay_stats`   — Theorem 1 & 2 analytic moments + Monte-Carlo oracle.
- :mod:`distributions` — pluggable miss-latency laws (Deterministic /
                         Exponential / Erlang / Hyperexponential / MC).
- :mod:`percentile`    — bounded-memory streaming quantile sketch (SLO tails).
- :mod:`ranking`       — eq. 16 variance-aware ranking + every §5.1 baseline.
- :mod:`simulator`     — vectorized lax.scan trace simulator.
- :mod:`hierarchy`     — two-tier sharded L1 -> shared L2 simulator.
- :mod:`sweep`         — batched multi-scenario sweep engine (vmap grids).
- :mod:`refsim`        — event-driven references (single + two-tier oracles).
- :mod:`trace`         — trace schema.
"""
from .delay_stats import (agg_mean_from_moments, agg_var_from_moments,
                          det_mean, det_var, stoch_mean, stoch_std, stoch_var)
from .distributions import (DISTRIBUTIONS, Deterministic, Erlang, Exponential,
                            Hyperexponential, MissLatency, MonteCarlo,
                            make_distribution)
from .hierarchy import (HierResult, HierTrace, make_hier_trace,
                        simulate_hier, simulate_hier_chunked)
from .percentile import QuantileSummary, StreamingQuantile
from .ranking import (BASELINES, OURS, POLICIES, Policy, PolicyParams,
                      Substrate, make_substrate)
from .simulator import (SimResult, SlotResult, latency_improvement,
                        simulate, simulate_chunked, simulate_stream)
from .sweep import HierSweepGrid, SweepGrid, sweep_grid, sweep_hier_grid
from .trace import (RequestStream, Trace, make_trace, stream_of_trace,
                    trace_of_stream)

__all__ = [
    "agg_mean_from_moments", "agg_var_from_moments",
    "det_mean", "det_var", "stoch_mean", "stoch_std", "stoch_var",
    "DISTRIBUTIONS", "Deterministic", "Erlang", "Exponential",
    "Hyperexponential", "MissLatency", "MonteCarlo", "make_distribution",
    "BASELINES", "OURS", "POLICIES", "Policy", "PolicyParams",
    "QuantileSummary", "StreamingQuantile",
    "Substrate", "make_substrate",
    "HierResult", "HierTrace", "make_hier_trace", "simulate_hier",
    "simulate_hier_chunked",
    "SimResult", "SlotResult", "latency_improvement", "simulate",
    "simulate_chunked", "simulate_stream",
    "HierSweepGrid", "SweepGrid", "sweep_grid", "sweep_hier_grid",
    "RequestStream", "Trace", "make_trace", "stream_of_trace",
    "trace_of_stream",
]
