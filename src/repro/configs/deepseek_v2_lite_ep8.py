"""deepseek-v2-lite-ep8: one chip's share of DeepSeek-V2-Lite under
8-way expert parallelism.

The model of ``deepseek_v2_lite`` holding routed experts 0-7 of 64: the
router keeps its 64 outputs and top-6, and each MoE layer computes the
part of its result that experts 0-7 give (plus the shared experts and
attention, which every chip of the layer computes alike).  What the
other 56 experts add would come from the other seven chips; nothing
here stands in for them.
"""
import dataclasses

from repro.configs.deepseek_v2_lite import CONFIG as _FULL

CONFIG = dataclasses.replace(_FULL, name="deepseek-v2-lite-ep8",
                             held_experts=(0, 8))
