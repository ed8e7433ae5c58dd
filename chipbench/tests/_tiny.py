"""A cell of the benchmark at a size the CPU can run in seconds."""
import time

from chipbench import bench, spec

TINY = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=4,
            head_dim=32, d_ff=256, vocab_size=1024)
SEED = 2**31 + 253            # past 32 signed bits, as a run's seed may be


# At this size the program's mean gap reads at most 4.2e-4 over four
# seeds and both mixes, and served from its int8 packs at most 6.5e-4
# (CPU); a broken step reads far above both.
TINY_LIMITS = {"mean_gap": 2e-3}
TINY_RATE = 2.0               # requests/s of an open loop


def cell(traffic: str):
    """The benchmark's first cell serving ``traffic`` (an open loop at
    ``TINY_RATE``), with a short ramp and check, held to this size's
    limit."""
    b = spec.benchmark()
    c = spec.cell(b["workloads"][0]["name"], b)
    c.traffic_name, c.traffic = traffic, spec.load_traffic(traffic)
    c.settings.update(ramp_s=1, check_tokens=48, limits=TINY_LIMITS,
                      rate=TINY_RATE)
    return c


def run(traffic: str, *, seconds=3.0, trace=False, quant=None, seed=SEED):
    c = cell(traffic)
    sizes = dict(c.model_sizes(), **TINY)
    return bench.run(c, seed, seconds, trace, t_start=time.perf_counter(),
                     require_tpu=False, sizes=sizes, quant=quant)
