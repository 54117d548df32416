"""Fig 13/17: allreduce algorithms — α-β model curves + measured HLO traffic
of our shard_map implementations on a 16-device mesh + flow-level achievable
bandwidth of the ring-allreduce traffic pattern per topology spec + netsim
*time-domain* simulations of the same algorithms as concrete collective
schedules played through each fabric (``coll=`` scenario leg), tying the
analytic curves to both the steady-state and the event-driven engines.

The ``sim/*`` rows are the contention-aware counterpart of the ``model/*``
rows: same algorithm, same payload, but completion time measured by
routing every phase's flows through the actual link graph
(:mod:`repro.netsim`).  The summary asserts the acceptance bars: simulated
ring allreduce on a healthy hx2-8x8 within 5% of the α-β model, and the
fluid-vs-simulated gap reported for the torus.
"""

import os
import subprocess
import sys

from repro.core import commodel as C
from repro.core import registry as R

from benchmarks import scenarios as S

SUITE = "fig13_allreduce"

FLOW_SPECS = ["hx2-8x8", "torus-16x16", "ft256"]
SIM_ALGOS = {  # per spec: the algorithms its geometry motivates
    "hx2-8x8": ("ring", "bidir", "hamiltonian"),
    "torus-16x16": ("ring", "torus"),
    "ft256": ("ring",),
}
SIM_SIZE = "s1GiB"


def scenarios(ctx: S.RunContext) -> list[S.Scenario]:
    out = [
        S.make(SUITE, f"model/p{p}", kind="model", p=p)
        for p in (64, 1024, 16384)
    ]
    out += [
        S.make(SUITE, f"flow/{spec}", topology=spec,
               pattern="ring-allreduce", kind="flow")
        for spec in FLOW_SPECS
    ]
    out += [
        S.make(SUITE, f"sim/{spec}/{algo}",
               scenario=f"{spec}/coll={algo}:{SIM_SIZE}", kind="sim")
        for spec in FLOW_SPECS
        for algo in SIM_ALGOS[spec]
    ]
    out.append(S.make(SUITE, "hlo", kind="hlo"))
    return out


def compute(sc: S.Scenario, ctx: S.RunContext) -> list[dict]:
    kind = sc.opts["kind"]
    if kind == "model":
        return _compute_model(sc.opts["p"])
    if kind == "flow":
        return _compute_flow(sc)
    if kind == "sim":
        return _compute_sim(sc)
    return _compute_hlo()


def _compute_sim(sc: S.Scenario) -> list[dict]:
    """Contention-aware simulated completion next to the analytic model."""
    parsed = sc.parsed()
    p = parsed.topology.num_accelerators
    sim_s = R.simulated_time(sc.scenario)
    model = parsed.collective.model_time(p)
    return [{
        "kind": "sim",
        "algo": parsed.collective.algo,
        "p": p,
        "sim_ms": round(sim_s * 1e3, 3),
        "model_ms": round(model * 1e3, 3) if model is not None else None,
        "ratio": round(sim_s / model, 4) if model is not None else None,
    }]


def summarize(results: list[tuple[S.Scenario, list[dict]]],
              ctx: S.RunContext) -> list[dict]:
    def _row(name):
        return next((r for sc, out in results for r in out
                     if sc.name == name), None)

    rows = []
    ring = _row("sim/hx2-8x8/ring")
    if ring is not None and ring["ratio"] is not None:
        rows.append({
            "kind": "sim",
            "ring_hx2_within_5pct": abs(ring["ratio"] - 1.0) <= 0.05,
            "ring_hx2_ratio": ring["ratio"],
        })
    torus = _row("sim/torus-16x16/torus") or _row("sim/torus-16x16/ring")
    if torus is not None and torus["ratio"] is not None:
        rows.append({
            "kind": "sim",
            "torus_fluid_gap": torus["ratio"],
            "torus_algo": torus["algo"],
        })
    return rows


def _compute_model(p: int) -> list[dict]:
    rows = []
    for size in (1e4, 1e6, 1e8, 1e9):
        name, t = C.best_algorithm(p, size)
        per = {n: f(p, size) for n, f in C.ALGORITHMS.items()}
        row = {"kind": "model", "p": p, "S": f"{size:.0e}", "best": name}
        row.update({n: round(size / t_ / C.INJECTION_BPS, 3)
                    for n, t_ in per.items()})
        rows.append(row)
    return rows


def _compute_flow(sc: S.Scenario) -> list[dict]:
    # the record's scenario string *is* the measurement key
    return [{"kind": "flow",
             "ring_allreduce": round(R.measured_fraction(sc.scenario), 3)}]


def _compute_hlo() -> list[dict]:
    # measured wire bytes of the JAX implementations (subprocess: fake devices)
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp, re
from jax.sharding import PartitionSpec as P
import sys
sys.path.insert(0, "src")
from repro.core import collectives as coll
mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
x = jax.ShapeDtypeStruct((1 << 20,), jnp.float32)  # 4 MiB
for algo in ("psum", "ring", "bidir", "torus", "hamiltonian"):
    lo = jax.jit(
        jax.shard_map(
            lambda v, a=algo: coll.allreduce(v, a, ("data", "model"), (4, 4)),
            mesh=mesh, check_vma=False, in_specs=P(), out_specs=P(),
        )
    ).lower(x)
    txt = lo.compile().as_text()
    n_perm = txt.count("collective-permute")
    n_ar = len(re.findall(r"all-reduce(?!-)", txt))
    print(f"MEASURE,{algo},permutes={n_perm},allreduces={n_ar}")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # fake host devices, never a chip
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=600,
    )
    rows = []
    for line in proc.stdout.splitlines():
        if line.startswith("MEASURE"):
            algo, perm, ar = line[len("MEASURE,"):].split(",")
            rows.append({"kind": "hlo", "algo": algo,
                         "permutes": int(perm.split("=")[1]),
                         "allreduces": int(ar.split("=")[1])})
    if proc.returncode != 0:
        rows.append({"kind": "hlo", "error": proc.stderr[-200:]})
    return rows
