"""The DeepSeek-V3-style MoE family (``"program": {"family": "moe"}``):
multi-head latent attention, leading dense layers, sparse layers of a
sigmoid router over all experts with a correction bias, the routed experts
held on this chip, and shared experts.  What the harness takes from a model
family (see ``families/dense.py``): the program's config object, the
reference's first training steps (``reference/mla_moe.py``), the step's
model operations and the smoke size.  No cell serves this family.
"""

from __future__ import annotations

import dataclasses

from chipbench import common
from chipbench.flops import causal_pairs
from chipbench.reference import mla_moe

train_reference = mla_moe.run

# ArchConfig fields the program needs for this family
NEEDS = ("kv_lora_rank", "experts_held", "expert_first", "router", "routed_scale",
         "shared_d_ff", "n_dense_layers", "dense_d_ff", "moe_aux_weight", "bias_rate")


def arch_config(config: dict, **extra):
    """The program's ``ArchConfig`` for a configuration file (HF keys of
    ``deepseek_v3``; the router's published width under ``published``)."""
    from repro.configs.base import ArchConfig

    have = {f.name for f in dataclasses.fields(ArchConfig)}
    if not have.issuperset(NEEDS):
        raise common.SpecError(f"the program cannot run family 'moe': its ArchConfig "
                               f"lacks {sorted(set(NEEDS) - have)}")
    c = config
    return ArchConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["moe_intermediate_size"],
        vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        tie_embeddings=c["tie_word_embeddings"],
        n_experts=c["published"]["n_routed_experts"], experts_held=c["n_routed_experts"],
        expert_first=c["program"]["expert_first"], top_k=c["num_experts_per_tok"],
        router=c["scoring_func"], routed_scale=float(c["routed_scaling_factor"]),
        moe_aux_weight=float(c["aux_loss_alpha"]), bias_rate=float(c["bias_update_speed"]),
        shared_d_ff=c["n_shared_experts"] * c["moe_intermediate_size"],
        n_dense_layers=c["first_k_dense_replace"], dense_d_ff=c["intermediate_size"],
        kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"], **extra)


def forward_flops(c: dict, batch: int, seq: int) -> int:
    """Model operations of one forward pass (a multiply-add is two), counting
    the routed experts held here at the tokens' expected share of their
    choices (k x held / experts), and causal attention's pairs."""
    d, h, L, dense = (c["hidden_size"], c["num_attention_heads"], c["num_hidden_layers"],
                      c["first_k_dense_replace"])
    nope, rope, vd, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
                         c["kv_lora_rank"])
    f, experts, held, k = (c["moe_intermediate_size"], c["published"]["n_routed_experts"],
                           c["n_routed_experts"], c["num_experts_per_tok"])
    tokens = batch * seq
    mla = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd) + h * vd * d
    sparse = (d * experts + 3 * d * c["n_shared_experts"] * f
              + 3 * d * f * k * held / experts)
    per_token = (L * mla + dense * 3 * d * c["intermediate_size"] + (L - dense) * sparse
                 + d * c["vocab_size"])
    attention = 2 * h * (nope + rope + vd) * batch * causal_pairs(seq) * L
    return int(2 * per_token * tokens + attention)


def train_step_flops(c: dict, batch: int, seq: int) -> int:
    """Forward and backward: three times the forward; recomputation not counted."""
    return 3 * forward_flops(c, batch, seq)


def serve_gaps(config, seed, dtype, requests, *, control=False, device=None):
    raise NotImplementedError("no cell serves the moe family")


SMALL = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
         "num_hidden_layers": 3, "n_routed_experts": 8, "vocab_size": 512}


def smoke_config(config: dict) -> dict:
    """Tiny widths that keep the structure: one dense layer then two sparse
    ones, 8 experts held of 16 routed, top 6, two shared experts, the router
    and its scale, latent attention."""
    c = dict(config, **SMALL)
    c["published"] = dict(config["published"], n_routed_experts=16)
    return c
