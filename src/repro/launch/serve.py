"""Batched serving driver: prefill a prompt batch, then autoregressive decode.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m-smoke \
      --batch 4 --prompt-len 32 --decode 64
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import make_batch
from repro.launch.cache import enable_compile_cache
from repro.models import get_model
from repro.obs import device as OD
from repro.train import steps as steps_lib


def make_step(cfg):
    """The jitted decode step.  The cache is donated: each step writes the
    next cache into the buffers of the last, so it is held once."""
    return jax.jit(steps_lib.make_decode_step(cfg), donate_argnums=(1,))


def generate(cfg, params, step_fn, prompts, n_decode):
    """Greedy continuation of ``prompts`` (B, P) by ``n_decode`` tokens.

    Returns ``(tokens (B, n_decode), prefill_s, decode_s)``; both times end
    when the device has finished.  Each call, phase and step is a host span
    on the profiler's trace (``repro.obs.device``).
    """
    batch, prompt_len = prompts.shape
    OD.trace_gc()
    with OD.span(OD.SERVE_GENERATE, call=OD.next_call(), batch=batch,
                 prompt=prompt_len, output=n_decode):
        cache = get_model(cfg).init_cache(cfg, batch, prompt_len + n_decode)
        # prefill via repeated decode steps (teacher-forced); serious serving
        # would run a single prefill forward — decode_32k / long_500k in the
        # dry-run measure the steady-state decode step this loop exercises.
        t0 = time.perf_counter()
        tok = None
        with OD.span(OD.SERVE_PREFILL, batch=batch, prompt=prompt_len):
            for t in range(prompt_len):
                with OD.span(OD.SERVE_STEP, step=t):
                    with OD.span(OD.SERVE_H2D):
                        x = jnp.asarray(prompts[:, t:t + 1])
                    tok, cache = step_fn(params, cache, x)
            tok.block_until_ready()
        prefill_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        out = []
        with OD.span(OD.SERVE_DECODE, batch=batch, output=n_decode):
            for i in range(n_decode):
                with OD.span(OD.SERVE_STEP, step=prompt_len + i):
                    tok, cache = step_fn(params, cache, tok)
                out.append(tok)
            with OD.span(OD.SERVE_FETCH):
                tokens = np.asarray(jnp.concatenate(out, axis=1))
        return tokens, prefill_s, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b-smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode", type=int, default=64)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    prompts = make_batch(cfg, args.prompt_len, args.batch)["tokens"]
    tokens, prefill_s, decode_s = generate(cfg, params, make_step(cfg),
                                           prompts, args.decode)
    toks_per_s = args.batch * args.decode / decode_s
    print(f"[serve] arch={cfg.name} batch={args.batch} "
          f"prefill {args.prompt_len} toks in {prefill_s:.2f}s; "
          f"decoded {args.decode} toks/seq in {decode_s:.2f}s "
          f"({toks_per_s:.1f} tok/s)")
    print(f"[serve] sample continuation: {tokens[0][:16].tolist()}")


if __name__ == "__main__":
    main()
