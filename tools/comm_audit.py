"""Collective-accounting audit of the compiled serving step (DESIGN.md §13).

  PYTHONPATH=src python tools/comm_audit.py --target tiny-target \
      --draft tiny-draft --tp 4 --devices 4

Wall-clock on CPU-emulated collectives is not a trustworthy gate, so the
throughput tensor-parallel ruleset is gated on what the compiler actually
emitted: this module walks post-GSPMD HLO, counts the collective ops
(all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute) and sums their output byte volumes — the per-step
communication bill a real interconnect would pay.

Two lowerings are audited. The GATE-bearing one is ``audit_forward``: the
model decode-window forward jitted with the params as EXPLICIT sharded
arguments, which is what a real deployment pays — weights resident as
sharded device buffers that XLA cannot constant-fold. The engine's fused
step (``audit_executor`` / ``Executor.step_hlo``) is recorded alongside
as a diagnostic. Its recorded numbers date from when the params entered
that jit as closure constants, which let XLA fold the exact ruleset's
weight/activation gathers on the tiny CI models and under-report its
traffic; the params are now step arguments, and the diagnostic has not
been re-recorded. The ``serve_sharded`` benchmark records both audits for both
rulesets in BENCH_serve.json and ``benchmarks.run --scenario sharded``
gates the forward-audit ratio (throughput must cut collective bytes
>= 2x vs exact on tp4 and bound all-reduces at <= 2 per layer).
"""
from __future__ import annotations

import re
from typing import Dict

# ops counted as collectives; async -start forms count once, -done forms
# (same transfer, second half of the pair) are skipped
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
}

# one HLO instruction: `%name = <result shape(s)> op-name(...`
_INSTR = re.compile(
    r"=\s+(?P<shape>[^=]*?)\s+(?P<op>[a-z0-9-]+)(?:-start)?\(")
# one array shape inside a result: `f32[2,4,64]` (layout suffix ignored)
_SHAPE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(shape_text: str) -> int:
    """Total bytes of every array in an HLO result shape (tuples sum)."""
    total = 0
    for dtype, dims in _SHAPE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


_WHILE_BODY = re.compile(r"body=%?([\w.\-]+)")


def collective_stats(hlo_text: str, *, loop_repeats: int = 1) -> Dict:
    """Count collectives and their byte volumes in compiled HLO text.

    Returns ``{"counts": {op: n}, "bytes": {op: n}, "total_count": n,
    "total_bytes": n}``; byte volume is the op's RESULT shape size (for an
    all-gather: the gathered array; for an all-reduce: the reduced array)
    — a device-count-independent proxy for the data each collective moves.

    ``loop_repeats``: a ``lax.scan`` over a stacked layer period compiles
    to a while loop whose body appears ONCE in the HLO text but executes
    per repeat — collectives inside while-BODY computations are therefore
    counted ``loop_repeats`` times (the scan trip count; collectives in
    the entry computation, e.g. hoisted weight reshards and the logits
    gather, stay at 1). Default 1 = raw static instruction counts.
    """
    bodies = (set(_WHILE_BODY.findall(hlo_text))
              if loop_repeats != 1 else set())
    counts = {op: 0 for op in COLLECTIVE_OPS}
    nbytes = {op: 0 for op in COLLECTIVE_OPS}
    mult = 1
    for line in hlo_text.splitlines():
        if ((line.startswith("%") or line.startswith("ENTRY"))
                and line.rstrip().endswith("{")):
            # computation header — while bodies get the repeat multiplier
            name = line.split("(", 1)[0].replace("ENTRY", "").strip()
            mult = loop_repeats if name.lstrip("%") in bodies else 1
            continue
        m = _INSTR.search(line)
        if m is None:
            continue
        op = m.group("op")
        if op.endswith("-done"):
            continue
        if op.endswith("-start"):
            op = op[:-len("-start")]
        if op not in counts:
            continue
        counts[op] += mult
        nbytes[op] += mult * _shape_bytes(m.group("shape"))
    return {
        "counts": {k: v for k, v in counts.items() if v},
        "bytes": {k: v for k, v in nbytes.items() if v},
        "total_count": sum(counts.values()),
        "total_bytes": sum(nbytes.values()),
    }


def forward_hlo(params, cfg, mesh, ruleset: str, *, batch: int = 2,
                width: int = 5) -> str:
    """Compiled HLO of the decode-window forward with the params as
    EXPLICIT jit arguments placed by the ruleset's ``param_specs`` — the
    collective pattern a deployment with resident sharded weights pays."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as _ops
    from repro.models import transformer
    from repro.sharding import specs as _specs

    pspecs = _specs.param_specs(params, mesh, serving=True, ruleset=ruleset)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def fwd(p, toks):
        logits, _, _ = transformer.forward(p, cfg, toks)
        return logits

    jitted = jax.jit(fwd,
                     in_shardings=(_specs.to_named(pspecs, mesh), repl),
                     out_shardings=repl)
    toks = jnp.zeros((batch, width), jnp.int32)
    with _ops.activation_mesh(mesh, ruleset):
        return jitted.lower(params, toks).compile().as_text()


def audit_forward(params, cfg, mesh, ruleset: str, **kw) -> Dict:
    """Collective stats of the params-as-arguments forward (the
    gate-bearing audit), plus the per-layer all-reduce bound. The layer
    stack lowers as a lax.scan, so per-layer collectives live in a while
    body — they are scaled by the scan trip count to get the true
    per-step bill (see ``collective_stats``)."""
    from repro.models import scan_plan
    repeats = max(1, scan_plan(cfg).n_repeats)
    stats = collective_stats(forward_hlo(params, cfg, mesh, ruleset, **kw),
                             loop_repeats=repeats)
    n_layers = max(1, cfg.num_layers)
    stats["n_layers"] = n_layers
    stats["all_reduces_per_layer"] = round(
        stats["counts"].get("all-reduce", 0) / n_layers, 4)
    stats["tp_ruleset"] = ruleset
    return stats


def audit_executor(ex, *, tree: bool = False,
                   any_sampled: bool = False) -> Dict:
    """Collective stats of one executor's fused decode step — DIAGNOSTIC
    only (the step contains several loops — draft scan, layer scans of
    two models — so static instruction counts are not scaled to
    executions)."""
    stats = collective_stats(ex.step_hlo(tree=tree, any_sampled=any_sampled))
    n_layers = max(1, ex.tc.num_layers)
    stats["n_layers"] = n_layers
    stats["all_reduces_per_layer"] = round(
        stats["counts"].get("all-reduce", 0) / n_layers, 4)
    stats["tp_ruleset"] = ex.tp_ruleset
    return stats


def audit_engine(engine, **kw) -> Dict:
    """Audit an Engine's (replica-0) executor step."""
    return audit_executor(engine.ex, **kw)


def main() -> int:
    """CLI: build a tiny engine per ruleset and print both audits."""
    import argparse
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--target", default="tiny-target")
    ap.add_argument("--draft", default="tiny-draft")
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--rulesets", default="exact,throughput")
    args = ap.parse_args()

    from repro.launch.mesh import ensure_host_devices, make_host_mesh
    ensure_host_devices(args.devices or args.tp)

    import jax
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.config import EngineConfig
    from repro.serving.engine import Engine

    tc, dc = get_config(args.target), get_config(args.draft)
    tparams = init_params(jax.random.PRNGKey(0), tc)
    dparams = init_params(jax.random.PRNGKey(1), dc)

    out = {}
    for ruleset in args.rulesets.split(","):
        eng = Engine(tparams, tc, dparams, dc, config=EngineConfig(
            mode="pard", k=4, max_batch=2, max_len=256, kv_block_size=16,
            mesh=make_host_mesh(model=args.tp, data=1),
            tp=args.tp, tp_ruleset=ruleset))
        out[ruleset] = audit_engine(eng)
    print(json.dumps(out, indent=2, sort_keys=True))
    if len(out) == 2:
        ex_b = out["exact"]["total_bytes"]
        th_b = out["throughput"]["total_bytes"]
        ratio = ex_b / max(1, th_b)
        print(f"# collective bytes exact/throughput = {ratio:.2f}x")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
