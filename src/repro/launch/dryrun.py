import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines, before ANY other import (jax locks the
# device count on first init). The 512 placeholder host devices exist ONLY
# for this dry-run; tests/benches see 1 device.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
        --shape decode_32k --multi-pod both --out results.jsonl

Training cells lower ``train_step`` (fp bf16 + AdamW); prefill/decode cells
lower the quantized serving step (paper W4A8 Integer Scale recipe) — that
is the deployment the paper targets. Failures here are bugs in the
framework's sharding.
"""
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, input_specs, shape_applicable
from repro.core.recipe import DEFAULT_RECIPE
from repro.distributed import sharding as shard
from repro.launch.mesh import axis_sizes, make_production_mesh
from repro.models.registry import get_arch, get_model, list_archs
from repro.nn import spec as S
from repro.training import optimizer as O
from repro.training.train_step import make_train_step

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "s4": 0.5, "u4": 0.5}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device collective buffer bytes from post-SPMD HLO, with a
    wire-traffic estimate per op semantics (ring algorithms)."""
    out = {c: {"count": 0, "bytes": 0.0, "wire_bytes": 0.0}
           for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.*)", ls)
        if m:
            ls = m.group(1)
        kind = None
        for c in COLLECTIVES:
            if re.match(rf"(\([^)]*\)|\S+)?\s*{c}[-\w]*\(", ls) or \
                    ls.startswith(c):
                kind = c
                break
        if kind is None:
            continue
        # result shape(s): leading "dt[dims]" or tuple "(dt[..], dt[..])"
        shapes = _SHAPE_RE.findall(ls.split(f"{kind}")[0])
        nbytes = 0.0
        for dt, dims in shapes:
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        # group size for wire factor
        gsz = 1
        gm = _GROUPS_RE.search(ls)
        if gm:
            gsz = int(gm.group(2))
        else:
            gb = _GROUPS_BRACE_RE.search(ls)
            if gb:
                gsz = len(gb.group(1).split(","))
        f = (gsz - 1) / max(gsz, 1)
        wire = {"all-reduce": 2 * f * nbytes,
                "all-gather": f * nbytes,
                "reduce-scatter": (gsz - 1) * nbytes,
                "all-to-all": f * nbytes,
                "collective-permute": 1.0 * nbytes}[kind]
        out[kind]["count"] += 1
        out[kind]["bytes"] += nbytes
        out[kind]["wire_bytes"] += wire
    out["total_bytes"] = sum(v["bytes"] for v in out.values()
                             if isinstance(v, dict))
    out["total_wire_bytes"] = sum(v["wire_bytes"] for v in out.values()
                                  if isinstance(v, dict))
    return out


def build_cell(arch: str, shape_name: str, mesh, multi_pod: bool):
    """Returns (lower_fn, meta) — lower_fn() does the actual lowering."""
    cfg = get_arch(arch)
    shp = SHAPES[shape_name]
    sizes = axis_sizes(mesh)
    data_ways = sizes.get("pod", 1) * sizes["data"]
    if cfg.num_experts:
        g = data_ways if (shp.batch * (shp.seq if shp.kind == "train" else 1)
                          ) % data_ways == 0 else 1
        cfg = dataclasses.replace(cfg, dispatch_groups=g)
    api = get_model(cfg)
    mode = shp.kind
    recipe = None if mode == "train" else DEFAULT_RECIPE
    rules = shard.rules_for(mode, multi_pod)
    pspecs = api.param_specs(cfg, recipe)
    pshard = shard.named_tree(mesh, pspecs, rules)
    inputs = input_specs(cfg, shp)
    ishard = shard.input_shardings(mesh, inputs, multi_pod)
    mem_key = ("image_embeds" if "image_embeds" in inputs
               else "frames" if "frames" in inputs else None)

    if mode == "train":
        ospecs = O.state_specs(pspecs)
        oshard = shard.named_tree(mesh, ospecs, rules)
        step = make_train_step(api, cfg, O.AdamWConfig())

        def lower():
            jitted = jax.jit(
                step,
                in_shardings=(pshard, oshard, ishard),
                out_shardings=(pshard, oshard, None),
            )
            return jitted.lower(S.abstract(pspecs), S.abstract(ospecs),
                                {k: v for k, v in inputs.items()})

        return lower, cfg

    cspecs = api.cache_specs(cfg, shp.batch, shp.seq)
    cshard = shard.named_tree(mesh, cspecs, rules)

    if mode == "prefill":
        def prefill_step(params, cache, inp):
            logits, cache, _ = api.apply(
                params, cfg, inp["tokens"], recipe=recipe, mode="prefill",
                cache=cache, pos=0,
                memory=inp.get(mem_key) if mem_key else None)
            return logits[:, -1], cache

        def lower():
            jitted = jax.jit(
                prefill_step,
                in_shardings=(pshard, cshard, ishard),
                out_shardings=(None, cshard),
            )
            return jitted.lower(S.abstract(pspecs), S.abstract(cspecs),
                                inputs)

        return lower, cfg

    # decode: one new token against a cache holding shp.seq tokens
    def serve_step(params, cache, inp, pos):
        logits, cache, _ = api.apply(
            params, cfg, inp["tokens"], recipe=recipe, mode="decode",
            cache=cache, pos=pos)
        return logits[:, 0], cache

    def lower():
        jitted = jax.jit(
            serve_step,
            in_shardings=(pshard, cshard, ishard, None),
            out_shardings=(None, cshard),
        )
        return jitted.lower(S.abstract(pspecs), S.abstract(cspecs), inputs,
                            jax.ShapeDtypeStruct((), jnp.int32))

    return lower, cfg


_VERDICT_RANK = ("fallback", "capped-alpha", "data-dependent", "certified",
                 "n/a")


def qcert_for(cfg) -> dict:
    """Static overflow verdicts for the serve recipe at this arch's
    contraction sizes (repro.analysis certificates, no tensors)."""
    from repro.core.recipe import certify_recipe

    dims = {"d_model": cfg.d_model, "d_ff": cfg.d_ff}
    if getattr(cfg, "moe_d_ff", 0):
        dims["moe_d_ff"] = cfg.moe_d_ff
    return certify_recipe(DEFAULT_RECIPE, dims)


def _qcert_worst(verdicts: dict) -> str:
    real = [v for v in verdicts.values() if v != "n/a"]
    if not real:
        return "n/a"
    return min(real, key=_VERDICT_RANK.index)


def run_cell(arch: str, shape_name: str, mesh, multi_pod: bool,
             collect_hlo: bool = True) -> dict:
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "multi_pod": multi_pod}
    cfg = get_arch(arch)
    shp = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shp)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    t0 = time.time()
    try:
        lower_fn, cfg2 = build_cell(arch, shape_name, mesh, multi_pod)
        with mesh:
            lowered = lower_fn()
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        rec.update(
            status="ok",
            lower_s=round(t1 - t0, 1),
            compile_s=round(t2 - t1, 1),
            param_count=cfg2.param_count_estimate(),
            memory={
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "alias_bytes": int(mem.alias_size_in_bytes),
                "code_bytes": int(mem.generated_code_size_in_bytes),
            },
            cost={
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                "transcendentals": float(cost.get("transcendentals", 0.0)),
            },
        )
        if shp.kind != "train":
            # serve cells run the quantized recipe: attach the static
            # overflow-certificate verdicts for its contraction sizes
            rec["qcert"] = qcert_for(cfg2)
            rec["qcert_worst"] = _qcert_worst(rec["qcert"])
        if collect_hlo:
            txt = compiled.as_text()
            rec["collectives"] = parse_collectives(txt)
            rec["hlo_convert_count"] = txt.count(" convert(")
            del txt
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--no-hlo", action="store_true")
    args = ap.parse_args()

    assert len(jax.devices()) == 512, "placeholder devices missing"
    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    pods = {"single": [False], "multi": [True],
            "both": [False, True]}[args.multi_pod]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    meshes = {mp: make_production_mesh(multi_pod=mp) for mp in pods}
    n_ok = n_skip = n_err = 0
    with open(args.out, "a") as f:
        for mp in pods:
            for arch in archs:
                for shape in shapes:
                    rec = run_cell(arch, shape, meshes[mp], mp,
                                   collect_hlo=not args.no_hlo)
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    tag = rec["status"]
                    n_ok += tag == "ok"
                    n_skip += tag == "skipped"
                    n_err += tag == "error"
                    msg = rec.get("error", rec.get("reason", ""))[:90]
                    extra = ""
                    if tag == "ok":
                        gb = rec["memory"]["argument_bytes"] / 2**30
                        extra = (f"args/dev={gb:.2f}GiB "
                                 f"flops/dev={rec['cost']['flops']:.3g} "
                                 f"lower={rec['lower_s']}s "
                                 f"compile={rec['compile_s']}s")
                        if "qcert_worst" in rec:
                            extra += f" qcert={rec['qcert_worst']}"
                    print(f"[{rec['mesh']}] {arch} x {shape}: {tag} "
                          f"{extra}{msg}", flush=True)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    _telemetry_cell()
    if n_err:
        raise SystemExit(1)


def _telemetry_cell() -> None:
    """Print the dry-run's registry snapshot: certificate verdicts, any
    quantization-health counters ticked while lowering the serve cells,
    and p50/p95/p99 for any ``*_seconds`` histograms (e.g. ptq/lowering
    spans) — everything here is eager/offline, the obs no-jit rule is
    moot."""
    from repro import obs

    snap = obs.default_registry().snapshot()
    c = snap["counters"]
    cells = []
    for name in ("qcert_verdicts_total", "quantized_layers_total",
                 "alpha_cap_events_total", "int_scale_floor_hits_total",
                 "amax_floor_hits_total"):
        if c.get(name):
            cells.append(f"{name}={c[name]}")
    print("[dryrun] telemetry: " + ("; ".join(cells) if cells
                                    else "no counters ticked"))
    for name, series in sorted(snap["histograms"].items()):
        if not name.endswith("_seconds"):
            continue
        for sk, st in sorted(series.items()):
            if not st["count"]:
                continue
            q = st["quantiles"]
            print(f"[dryrun] {name}{{{sk}}}: n={st['count']} "
                  f"p50={q['p50'] * 1e3:.2f}ms "
                  f"p95={q['p95'] * 1e3:.2f}ms "
                  f"p99={q['p99'] * 1e3:.2f}ms")


if __name__ == "__main__":
    main()
