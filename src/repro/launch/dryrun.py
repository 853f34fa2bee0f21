import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay the first statements in this file — jax
locks the device count on first init, and the dry-run needs 512 virtual
host devices to build the production meshes.  Nothing else in the repo
sets this flag (smoke tests and benches see the real single CPU).

Per cell this driver:
  1. builds the full-size architecture config (no allocation — params,
     optimizer state, caches are all ShapeDtypeStructs),
  2. jit's the train/prefill/decode step with explicit in/out shardings,
  3. ``.lower(...)`` then ``.compile()`` — a failure here (sharding
     mismatch, collective error, OOM-at-compile) is a bug in the system,
  4. records ``memory_analysis()`` / ``cost_analysis()`` and the
     collective bytes parsed from the optimized HLO,
  5. derives the three roofline terms (compute / HBM / interconnect).

Conventions: the compiled module is the per-device SPMD program, so FLOPs
and bytes from ``cost_analysis()`` are **per device**; roofline terms
divide by *per-chip* peak rates.  Collective bytes sum the operand sizes
of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops (async ``-start`` counted once, ``-done`` skipped).

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun.json
  python -m repro.launch.dryrun --arch qwen2-7b --shape decode_32k --multi-pod
"""

import argparse
import json
import re
import sys
import time

import jax

# TPU v5e hardware constants (per chip).
PEAK_FLOPS = 197e12       # bf16
HBM_BW = 819e9            # bytes/s
ICI_BW = 50e9             # bytes/s per link — conservative single-link figure

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\("
)
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+|[\w.\-]+)\s*=\s*")
_NAME_RE = re.compile(r"%[\w.\-]+")


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in the optimized HLO.

    Optimized HLO omits operand shape annotations, so a first pass records
    every instruction's *output* bytes by name; collective operand names
    are then resolved against that table ("sum operand sizes" — the bytes
    each device contributes to the wire).
    """
    out_bytes: dict[str, float] = {}
    lines = hlo_text.splitlines()
    for line in lines:
        m = _DEF_RE.match(line)
        if not m or "=" not in line:
            continue
        name = m.group(1)
        rest = line[m.end():]
        head = rest.split("(", 1)[0]  # output type (possibly a tuple)
        nb = sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(head))
        if nb:
            out_bytes[name.lstrip("%")] = float(nb)

    per_kind: dict[str, float] = {}
    count = 0
    for line in lines:
        m = _COLL_RE.search(line)
        if not m:
            continue
        opcode_seg = line.split("=", 1)[1] if "=" in line else line
        if re.search(r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)-done\b", opcode_seg):
            continue
        kind = m.group(1)
        start = m.end() - 1  # the call '(' — regex ends with '\('
        depth, end = 0, start
        for i in range(start, len(line)):
            if line[i] == "(":
                depth += 1
            elif line[i] == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operands = line[start + 1 : end]
        # explicit annotations first; fall back to name resolution
        nbytes = sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(operands))
        if nbytes == 0:
            for nm in _NAME_RE.findall(operands):
                nbytes += out_bytes.get(nm.lstrip("%"), 0.0)
        per_kind[kind] = per_kind.get(kind, 0.0) + float(nbytes)
        count += 1
    per_kind["total"] = float(sum(v for k, v in per_kind.items() if k != "total"))
    per_kind["num_ops"] = count
    return per_kind


def model_flops(cfg, shape) -> float:
    """6·N_active·D for train, 2·N_active·D for inference (global)."""
    n_act = cfg.active_param_count()
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_act * shape.tokens


def _depth_variant(cfg, n_layers: int):
    """Same architecture at a reduced layer count (divisibility-aware)."""
    import dataclasses

    kw = {"n_layers": n_layers}
    if cfg.encoder_layers:
        kw["encoder_layers"] = n_layers
    return dataclasses.replace(cfg, **kw)


def _probe_depths(cfg, *, scale: int = 4) -> tuple[int, int]:
    """Two reduced depths compatible with the arch's grouping constraints.

    Larger probes give a cleaner per-layer slope (XLA picks slightly
    different fusion/collective strategies per depth; at depth 4–8 the
    layer term dominates that noise).
    """
    step = 1
    if cfg.shared_attn_every:
        step = max(step, cfg.shared_attn_every)
    if cfg.slstm_every:
        step = max(step, cfg.slstm_every)
    base = cfg.moe.first_dense_layers if (cfg.moe and cfg.moe.first_dense_layers) else 0
    return base + scale * step, base + 2 * scale * step


def _measure_cell(cfg, shape, mesh, *, unroll_layers: bool = False, **build_kw) -> dict:
    """Lower+compile one concrete config; return raw per-device terms.

    ``unroll_layers=True`` fully unrolls the layer scans so every body is
    visible to cost_analysis — required by the depth probes (a rolled scan
    of length 2 is still a while loop counted once).
    """
    from repro.launch.specs import build_cell
    from repro.models import attention as attn_lib
    from repro.models import transformer as tf

    from repro.runtime import sharding as shard_lib

    decode_flash = build_kw.pop("decode_flash", False)
    expert_mode = build_kw.pop("expert_mode", "ep_model")
    if unroll_layers:
        tf.set_layer_scan_unroll(True)
    attn_lib.set_decode_flash_partitioning(decode_flash)
    shard_lib.set_expert_sharding(expert_mode)
    try:
        cell = build_cell(cfg, shape, mesh, **build_kw)
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                cell.step_fn,
                in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings,
                donate_argnums=cell.donate_argnums,
            )
            lowered = jitted.lower(*cell.arg_shapes)
            compiled = lowered.compile()
    finally:
        if unroll_layers:
            tf.set_layer_scan_unroll(1)
        attn_lib.set_decode_flash_partitioning(False)
        shard_lib.set_expert_sharding("ep_model")
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict] per computation
        cost = cost[0] if cost else {}
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll["total"],
        "coll_by_kind": coll,
        "memory_analysis": compiled.memory_analysis(),
        "hlo": None,  # dropped to keep memory bounded
    }


def depth_corrected_terms(cfg, shape, mesh, *, probe_scale: int = 4, **build_kw) -> dict:
    """Fix the while-loop single-count: measure at two reduced depths,
    fit term(L) = a + b·L, extrapolate to the full layer count.

    XLA's cost_analysis (and HLO text) count a while body ONCE regardless
    of trip count, so scan-over-layers models under-report FLOPs/bytes/
    collective bytes by ~L×.  The linear fit recovers the per-layer body
    cost b exactly and the loop-invariant overhead a (embed, head, optimizer,
    top-level collectives).  Caveat: *sequence*-level scans inside a layer
    (chunked attention, recurrent cells) are still counted once — the
    analytic terms reported alongside bound that residual.
    """
    lo, hi = _probe_depths(cfg, scale=probe_scale)
    lo = min(lo, cfg.n_layers)
    hi = min(hi, cfg.n_layers)
    m_lo = _measure_cell(_depth_variant(cfg, lo), shape, mesh,
                         unroll_layers=True, **build_kw)
    if hi == lo:
        return {k: m_lo[k] for k in ("flops", "bytes", "coll")}
    m_hi = _measure_cell(_depth_variant(cfg, hi), shape, mesh,
                         unroll_layers=True, **build_kw)
    out = {}
    for k in ("flops", "bytes", "coll"):
        b = (m_hi[k] - m_lo[k]) / (hi - lo)
        a = m_lo[k] - b * lo
        out[k] = max(a + b * cfg.n_layers, m_hi[k])
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, n_micro: int = 1,
             fsdp: bool = True, remat: bool = True, vocab_chunk: int = 0,
             cache_prefer: str = "largest", depth_correct: bool = False,
             decode_flash: bool = False, expert_mode: str = "ep_model",
             verbose: bool = True) -> dict:
    from repro.configs import get_config, get_shape
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_cell

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "skipped": "full-attention arch: long_500k needs sub-quadratic mixing"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    build_kw = dict(n_micro=n_micro, fsdp=fsdp, remat=remat,
                    vocab_chunk=vocab_chunk, cache_prefer=cache_prefer,
                    decode_flash=decode_flash, expert_mode=expert_mode)
    from repro.models import attention as attn_lib
    from repro.runtime import sharding as shard_lib

    t0 = time.time()
    bk = dict(build_kw)
    bk.pop("decode_flash")
    bk.pop("expert_mode")
    shard_lib.set_expert_sharding(expert_mode)
    cell = build_cell(cfg, shape, mesh, **bk)

    attn_lib.set_decode_flash_partitioning(decode_flash)
    try:
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                cell.step_fn,
                in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings,
                donate_argnums=cell.donate_argnums,
            )
            lowered = jitted.lower(*cell.arg_shapes)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    finally:
        attn_lib.set_decode_flash_partitioning(False)
        shard_lib.set_expert_sharding("ep_model")

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict] per computation
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    n_chips = mesh.devices.size

    raw_terms = {
        "compute_s": flops_dev / PEAK_FLOPS,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll["total"] / ICI_BW,
    }

    # --- depth-corrected terms (fixes the while-body single-count) -----
    if depth_correct and cfg.n_layers > 2:
        corr = depth_corrected_terms(cfg, shape, mesh, probe_scale=4, **build_kw)
        terms = {
            "compute_s": corr["flops"] / PEAK_FLOPS,
            "memory_s": corr["bytes"] / HBM_BW,
            "collective_s": corr["coll"] / ICI_BW,
        }
        flops_dev_corr = corr["flops"]
    else:
        terms = dict(raw_terms)
        flops_dev_corr = flops_dev
    dominant = max(terms, key=terms.get)

    # --- analytic cross-check (no loop-count issues at all) ------------
    from repro.profilers.program import stage_specs

    stages = stage_specs(cfg, shape, group=1)
    analytic = {
        "compute_s": sum(s_.flops for s_ in stages) / (n_chips * PEAK_FLOPS),
        "memory_s": sum(s_.bytes_hbm for s_ in stages) / (n_chips * HBM_BW),
    }

    mf = model_flops(cfg, shape)
    hlo_flops_global = flops_dev_corr * n_chips
    result = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "mesh": list(mesh.devices.shape),
        "chips": n_chips,
        "kind": shape.kind,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None)
            if hasattr(mem, "peak_memory_in_bytes")
            else None,
        },
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collectives": coll,
        "roofline": {
            **{k: v for k, v in terms.items()},
            "dominant": dominant,
            "step_time_s": max(terms.values()),
        },
        "roofline_raw": raw_terms,
        "analytic": analytic,
        "model_flops_global": mf,
        "hlo_flops_global": hlo_flops_global,
        "useful_flops_ratio": mf / hlo_flops_global if hlo_flops_global else None,
    }
    if verbose:
        mb = (result["memory"]["argument_bytes"] or 0) / 2**30
        print(
            f"[dryrun] {arch:>24s} × {shape_name:<12s} mesh={result['mesh']} "
            f"lower={t_lower:.0f}s compile={t_compile:.0f}s "
            f"args={mb:.2f}GiB/dev flops/dev={flops_dev:.3e} "
            f"coll={coll['total']:.3e}B dominant={dominant}",
            flush=True,
        )
    return result


def main(argv=None) -> int:
    from repro.configs import valid_cells

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--shard", help="K/N — run the K-th of N slices of --all")
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--vocab-chunk", type=int, default=0)
    ap.add_argument("--cache-prefer", default="largest", choices=["largest", "last"])
    ap.add_argument("--depth-correct", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    if args.all:
        cells = valid_cells()
        if args.shard:
            k, n = map(int, args.shard.split("/"))
            cells = [c for i, c in enumerate(cells) if i % n == k]
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    results = []
    failures = 0

    def flush_out():
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    for arch, shape in cells:
        for mp in meshes:
            try:
                results.append(
                    run_cell(arch, shape, multi_pod=mp, n_micro=args.n_micro,
                             fsdp=not args.no_fsdp, remat=not args.no_remat,
                             vocab_chunk=args.vocab_chunk,
                             cache_prefer=args.cache_prefer,
                             depth_correct=args.depth_correct)
                )
            except Exception as e:  # noqa: BLE001 — report, continue, fail at exit
                failures += 1
                print(f"[dryrun] FAIL {arch} × {shape} multi_pod={mp}: {e!r}",
                      flush=True)
                results.append(
                    {"arch": arch, "shape": shape, "multi_pod": mp,
                     "error": repr(e)}
                )
            flush_out()  # incremental — a crash loses at most one cell
    if args.out:
        print(f"[dryrun] wrote {len(results)} cells → {args.out}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
