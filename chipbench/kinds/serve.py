"""A serving cell: closed-loop waves through the program's ``generate``.

The program serves one batch of equal-length prompts per ``generate`` call
and sizes its cache to prompt + output, so the load is a closed loop of
waves: each wave is ``batch`` requests of one (prompt, output) shape from
the traffic file, and falls due when the previous wave completes.  The waves
come in blocks that hold each shape once, in an order drawn from the seed;
the window starts blocks until ``--seconds`` have passed and ends only at the
end of a block, so every seed serves the same mix.  Every shape's decode
program is compiled, and the small programs ``generate`` runs around it are
warmed, in set-up.
"""

from __future__ import annotations

import numpy as np

from chipbench import common, compare
from chipbench.reference import weights as W

PROMPT_STREAM = 1 << 30  # prompt rows of wave i come from index PROMPT_STREAM + i


class FirstTokenTap:
    """The decode step as ``generate`` calls it, keeping the token the last
    prompt step produced: ``generate`` feeds it back but does not return it,
    and the check compares it too."""

    def __init__(self, step, prompt_len: int):
        self.step, self.prompt_len, self.calls, self.first = step, prompt_len, 0, None

    def __call__(self, params, cache, tok):
        out = self.step(params, cache, tok)
        self.calls += 1
        if self.calls == self.prompt_len:
            self.first = out[0]
        return out


def prompts_of(seed: int, wave: int, batch: int, prompt: int, vocab: int) -> np.ndarray:
    return common.lm_rows(seed, PROMPT_STREAM + wave, batch, prompt, vocab)


def run(ctx) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch import serve
    from repro.models import get_model

    mix, config, family = ctx.traffic, ctx.config, ctx.family
    cfg = family.arch_config(config)
    model = get_model(cfg)
    batch, shapes = mix["batch"], [(s["prompt"], s["output"]) for s in mix["waves"]]
    dtype = jnp.dtype(config["torch_dtype"])
    key = W.root_key(common.key_words(ctx.seed))
    template = jax.eval_shape(lambda: model.init_params(cfg, jax.random.PRNGKey(0),
                                                        dtype=dtype))
    params = jax.jit(lambda k: W.make_params(template, k, tied=cfg.tie_embeddings))(key)

    step = serve.make_step(cfg)
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    compiled = {}
    for p, o in shapes:
        cache = jax.eval_shape(lambda n=p + o: model.init_cache(cfg, batch, n))
        compiled[(p, o)] = exe = step.lower(params, cache, tok).compile()
        # what generate runs around the step: the empty cache and the
        # concatenation of the served tokens, at this shape
        first = exe(params, model.init_cache(cfg, batch, p + o), jnp.zeros((batch, 1), jnp.int32))
        jnp.concatenate([first[0]] * o, axis=1).block_until_ready()
    del first
    ctx.setup_done()

    order = common.wave_order(ctx.seed, len(shapes), 1 << 16)
    waves, latencies = [], []
    with ctx.window():
        due = ctx.clock()
        while ctx.elapsed() < ctx.seconds or len(waves) % len(shapes):
            i = len(waves)
            p, o = shapes[order[i]]
            with ctx.span("cb.wave_setup"):
                prompts = prompts_of(ctx.seed, i, batch, p, cfg.vocab)
                tap = FirstTokenTap(compiled[(p, o)], p)
            with ctx.span("cb.generate"):
                tokens, _, _ = serve.generate(cfg, params, tap, prompts, o)
            done = ctx.clock()
            latencies.extend([done - due] * batch)
            due = done
            waves.append((p, o, prompts, np.asarray(tap.first), tokens))
    window_s = ctx.window_s
    served = sum(tokens.shape[0] * tokens.shape[1] for *_, tokens in waves)
    failed = sum(int(not (t.shape == (o,) and ((t >= 0) & (t < cfg.vocab)).all()))
                 for p, o, _, _, tokens in waves for t in tokens)
    ctx.report(
        attempted=len(latencies), failed=failed,
        e2e={"serve_tokens_per_s": served / window_s,
             "request_latency_p95_ms": 1e3 * float(np.percentile(latencies, 95))},
        layer_inputs={"waves": [(p, o) for p, o, *_ in waves],
                      "flops": sum(family.wave_flops(config, batch, p, o) for p, o, *_ in waves),
                      "bytes": sum(family.wave_bytes(config, batch, p, o) for p, o, *_ in waves)})
    ctx.read_memory()
    del params, compiled, tap
    jax.clear_caches()

    requests = ctx.sampled = sample(waves, ctx.seed, mix["check_requests"])
    gaps = family.serve_gaps(config, ctx.seed, dtype, requests, device=ctx.devices[0])
    ctx.check("served_gap", compare.served_gap(gaps), mix["limits"]["served_gap"])
    ctx.check("compiles_in_window", ctx.window_lowerings, 0)


def sample(waves, seed: int, n: int) -> list:
    """``n`` requests drawn from the seed, one of them from the longest wave
    -> [(prompt, served), ...] with served = the first token and the output."""
    pool = [(w, r) for w in range(len(waves)) for r in range(waves[w][2].shape[0])]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    longest = max(range(len(waves)), key=lambda w: waves[w][0] + waves[w][1])
    rows = [pool.index((longest, int(rng.integers(waves[longest][2].shape[0]))))]
    rest = [i for i in rng.permutation(len(pool)) if i not in rows]
    rows += rest[:max(0, n - 1)]
    out = []
    for i in rows:
        w, r = pool[i]
        _, _, prompts, first, tokens = waves[w]
        out.append((prompts[r], np.concatenate([first[r], tokens[r]])))
    return out
