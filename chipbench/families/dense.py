"""The dense decoder family (Llama-style: RMSNorm, rotary positions, grouped-
query attention, SwiGLU): what the harness takes from a model family.

A configuration's family is the one its ``program`` group names, the
program's own family (``"program": {"family": "dense"}``), and its module is
``chipbench/families/<family>.py``.  A family module gives

* ``arch_config(config, **extra)``: the program's config object;
* ``train_reference(config, job, seed, *, steps, dtype, precision, fault,
  chips, device)``: the reference's first training steps, which a training
  cell's readings are compared with, and its control and faults;
* ``serve_gaps(config, seed, dtype, requests, *, control, device)``: the gap
  of each served token below the reference's best, and the control's;
* ``train_step_flops(config, batch, seq)``, ``wave_flops(config, batch,
  prompt, output)``, ``wave_bytes(config, batch, prompt, output)``: the
  model operations and least bytes the step metrics count;
* ``smoke_config(config)``: the configuration at a size a CPU test runs.
"""

from __future__ import annotations

from chipbench import common, flops
from chipbench.reference import serve_ref, train_ref

arch_config = common.arch_config
train_reference = train_ref.run
serve_gaps = serve_ref.gaps
train_step_flops = flops.train_step_flops
wave_flops = flops.wave_flops
wave_bytes = flops.wave_bytes

SMALL = {"hidden_size": 64, "intermediate_size": 128, "head_dim": 16,
         "vocab_size": 512, "num_hidden_layers": 2}


def smoke_config(config: dict) -> dict:
    """Tiny widths that keep the layout: query heads per kv head (up to 4),
    tying and theta."""
    c = dict(config, **SMALL)
    groups = config["num_attention_heads"] // config["num_key_value_heads"]
    c["num_attention_heads"], c["num_key_value_heads"] = 4, 4 // min(groups, 4)
    return c
