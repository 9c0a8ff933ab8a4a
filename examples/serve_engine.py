"""Serving-engine policy A/B: a Zipf stream of prompt prefixes through
``ServeEngine``'s delayed-hit prefix cache under four eviction policies, with
stochastic prefill latency.

    PYTHONPATH=src python examples/serve_engine.py
"""
import numpy as np

from repro.serving.engine import LatencyModel, ServeEngine


def policy_ab_demo():
    rng = np.random.default_rng(1)
    n_prefix = 200
    probs = (np.arange(1, n_prefix + 1) ** -0.9)
    probs /= probs.sum()
    lengths = rng.integers(128, 4096, n_prefix)
    times, keys, lens = [], [], []
    t = 0.0
    for _ in range(20_000):
        t += rng.exponential(0.002)
        k = int(rng.choice(n_prefix, p=probs))
        times.append(t); keys.append(f"p{k}"); lens.append(int(lengths[k]))
    print("[prefix cache A/B] 20k requests, 200 Zipf prefixes, "
          "stochastic prefill latency:")
    for policy in ("lru", "lhd", "vacdh", "stoch_vacdh"):
        eng = ServeEngine(capacity=60_000.0, policy=policy,
                          latency=LatencyModel(base_s=0.03, per_token_s=2e-5),
                          state_size_fn=lambda n: float(n), seed=7)
        s = eng.run_trace(times, keys, lens).as_dict()
        print(f"  {policy:12s} total_latency={s['total_latency']:9.2f}s "
              f"hits={s['hits']:6d} delayed={s['delayed_hits']:5d} "
              f"misses={s['misses']:5d} hedges={s['hedges']}")


if __name__ == "__main__":
    policy_ab_demo()
