"""Each cell of ``BENCHMARK.json`` cut to a size a CPU test can hold:
the same files, drivers and checks, with small widths and few layers."""
from bench.lib import harness

SMALL_CONFIG = {
    "h2o-danube-3-4b": dict(hidden_size=128, num_hidden_layers=2,
                            num_attention_heads=4, num_key_value_heads=2,
                            head_dim=32, intermediate_size=256,
                            vocab_size=512),
    "granite-20b": dict(n_embd=256, n_layer=2, n_head=4, n_inner=512),
}
# The cells' own limits hold at these sizes except the logit gap, which
# is wider at full width: here sound runs read 0 to 0.014 and the fp8
# control 0.079 to 0.171 (CPU, seeds 1-5), so the small serve cell's
# limit is 0.035.
SMALL_WORKLOAD = {
    "engine": dict(rows=256, trace_seconds=1, check_layers=2),
    "serve": dict(bucket=[4, 64], max_seq=96, batch=4, new_tokens=16,
                  token_max=512, trace_seconds=1,
                  check_requests=8, limits={"logit_gap": 0.035},
                  lengths={"dist": "lognormal", "median": 24, "sigma": 0.6,
                           "min": 8, "max": 64}),
}


def cell(name):
    c = harness.load_cell(name)
    c.config = dict(c.config, **SMALL_CONFIG[c.config["name"]])
    c.workload.update(SMALL_WORKLOAD[c.workload["driver"]])
    return c


def run(c, seed=2**33 + 17, seconds=0.5, trace=False):
    import time

    import jax

    from bench.lib.peaks import PEAKS
    return harness.driver(c).run(
        c, jax.devices()[:c.chips], PEAKS["TPU v5 lite"], seed=seed,
        seconds=seconds, trace=trace, t0=time.perf_counter())
