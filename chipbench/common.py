"""What every cell shares: the spec, seeds, the traffic generator, peaks,
the host clock and the device's peak memory.

Nothing here imports the program (``repro``) except :func:`arch_config`,
which builds the program's config object from a configuration file.  What
belongs to one model family (its reference, operation counts and smoke size)
is in ``chipbench/families/<family>.py``, found by :func:`family` from the
family the configuration's ``program`` group names.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = pathlib.Path(__file__).resolve().parent
OUT = HERE / "out"

# JAX's monitoring event for every program lowered to MLIR: a new shape or a
# new function.  The window must see none.
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class SpecError(Exception):
    """The benchmark's files do not name what was asked for."""


def load_spec(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no {path}")
    return json.loads(path.read_text())


def cell(spec: dict, root: pathlib.Path, name: str) -> tuple[dict, dict, dict]:
    """-> (workload entry, configuration file, traffic file) of cell ``name``."""
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise SpecError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config {w['config']!r}")
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic_path = root / "chipbench" / "traffic" / f"{w['traffic']}.json"
    if not traffic_path.is_file():
        raise SpecError(f"no traffic file {traffic_path}")
    return w, config, json.loads(traffic_path.read_text())


def family(root: pathlib.Path, config: dict):
    """The module of ``config``'s model family: ``chipbench/families/<family>.py``
    under ``root``, for the family its ``program`` group names."""
    name = config["program"]["family"]
    path = root / "chipbench" / "families" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"configuration {config['name']!r} names family {name!r}: no {path}")
    return load_module(path, f"chipbench_family_{name}")


_LOADED: dict = {}  # resolved path -> module


def load_module(path: pathlib.Path, name: str):
    """The Python file ``path`` as the module ``name``: a file of a checkout,
    which need not be on the import path.  Each file runs once a process,
    and is in ``sys.modules`` while it runs (a dataclass needs that)."""
    path = pathlib.Path(path).resolve()
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
        _LOADED[path] = mod
    return _LOADED[path]


def end_to_end_names(spec: dict, workload: str) -> list[str]:
    return [m["name"] for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])]


def per_layer_entries(spec: dict, workload: str) -> list[dict]:
    """The per-layer metrics whose ``workloads`` list this cell."""
    return [m for m in spec["per_layer"] if workload in m["workloads"]]


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({sorted(table)}); add its published peaks")
    return table[device_kind]


# ---------------------------------------------------------------------------
# seeds and traffic
# ---------------------------------------------------------------------------


def key_words(seed: int) -> np.ndarray:
    """Two 32-bit words from any whole ``seed`` (also past 2**32): a raw
    threefry key, as ``jax.random.PRNGKey`` makes."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def zipf_probs(vocab: int, alpha: float = 1.1) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** (-alpha)
    return p / p.sum()


def lm_rows(seed: int, index: int, rows: int, cols: int, vocab: int,
            alpha: float = 1.1) -> np.ndarray:
    """``rows x cols`` token ids, Zipf over the first min(vocab, 4096) ids,
    from ``(seed, index)``.  The same arithmetic as the program's synthetic
    LM batch (``data/pipeline.SyntheticLM.batch``), kept here so that the
    reference reads its rows from the benchmark and not from the program."""
    rng = np.random.default_rng([int(seed), int(index)])
    p = zipf_probs(min(vocab, 4096), alpha)
    return rng.choice(len(p), size=(rows, cols), p=p).astype(np.int32)


def train_rows(seed: int, step: int, batch: int, seq: int, vocab: int):
    """(tokens, labels) of training step ``step``: what ``make_batch``
    feeds the program."""
    toks = lm_rows(seed, step, batch, seq + 1, vocab)
    return toks[:, :-1], toks[:, 1:]


def wave_order(seed: int, n_shapes: int, n_waves: int) -> list[int]:
    """Shape index of each wave: every block of ``n_shapes`` waves holds each
    shape once, in an order drawn from the seed, so that every seed serves
    the same mix."""
    rng = np.random.default_rng([int(seed), 0x5E4E])
    order: list[int] = []
    while len(order) < n_waves:
        order.extend(int(i) for i in rng.permutation(n_shapes))
    return order[:n_waves]


# ---------------------------------------------------------------------------
# the program's config object, from a configuration file
# ---------------------------------------------------------------------------


# ArchConfig field <- HF key, for the keys every decoder's file holds
HF_KEYS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
           "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
           "d_ff": "intermediate_size", "vocab": "vocab_size", "head_dim": "head_dim",
           "rope_theta": "rope_theta", "tie_embeddings": "tie_word_embeddings"}


def arch_config(config: dict, **extra):
    """The program's ``ArchConfig`` for a configuration file: its HF keys,
    and its ``program`` group for what HF does not name (the family, and
    any field of another family such as experts)."""
    from repro.configs.base import ArchConfig

    fields = {f: config[k] for f, k in HF_KEYS.items()}
    return ArchConfig(name=config["name"], **fields, **config["program"], **extra)


# ---------------------------------------------------------------------------
# host clock and device memory (as chip_smoke.py reads them)
# ---------------------------------------------------------------------------


def now() -> float:
    return time.perf_counter()


def peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest of ``devices``; None where the
    backend keeps no count (the CPU)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


class LowerCounter:
    """Counts the programs lowered inside a ``with`` block (JAX monitoring)."""

    def __init__(self):
        self.count = 0

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == LOWER_EVENT:
            self.count += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_event)
