import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# The two lines above MUST run before any jax import (jax locks the device
# count at first init). Everything below is ordinary code.

"""Multi-pod dry-run driver.

For every (architecture × input shape × mesh) cell this lowers the real
train/prefill/decode step with ShapeDtypeStruct stand-ins (no allocation),
compiles it for the production mesh, and records:

  * memory_analysis()       — proves the cell fits per-device HBM;
  * cost_analysis()         — HLO FLOPs / bytes for the roofline;
  * collective bytes        — parsed from the compiled per-device HLO
                              (all-gather / all-reduce / reduce-scatter /
                              all-to-all / collective-permute);
  * the three roofline terms and the MODEL_FLOPS/HLO_FLOPs ratio.

Artifacts land in experiments/artifacts/<arch>_<shape>_<mesh>.json and
are the inputs to benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
    python -m repro.launch.dryrun --all [--mesh both] [--force]
    python -m repro.launch.dryrun --arch mamba2-370m --shape train_4k
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "..", "..", "..", "experiments", "artifacts")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLL_RE = re.compile(
    r"=\s+(?:\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(pred|[su]\d+|bf16|f16|f32|f64)\[([\d,]*)\]")

# approximate wire-bytes multiplier on the *result* bytes of each op
_WIRE_MULT = {
    "all-gather": 1.0,        # each device receives ~result bytes
    "all-reduce": 2.0,        # ring: reduce-scatter + all-gather
    "reduce-scatter": 1.0,    # sends ~operand ≈ result × N; use operands
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

# per-cell microbatch count for train cells (activation-memory knob;
# chosen during §Perf iteration so every cell fits 16 GiB HBM)
DEFAULT_MICRO: Dict = {}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes per collective kind from partitioned HLO."""
    out = {k: 0.0 for k in _WIRE_MULT}
    counts = {k: 0 for k in _WIRE_MULT}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        shapes = _SHAPE_RE.findall(line)
        if not shapes:
            continue
        if kind == "reduce-scatter" and len(shapes) > 1:
            nbytes = sum(_shape_bytes(d, s) for d, s in shapes[1:])
        else:
            nbytes = _shape_bytes(*shapes[0])
        out[kind] += nbytes * _WIRE_MULT[kind]
        counts[kind] += 1
    out_counts = {f"n_{k}": v for k, v in counts.items()}
    return {**out, **out_counts}


def n_active_params(cfg) -> int:
    """Params touched per token (MoE: top-k of experts), excl. embeddings."""
    total = cfg.n_params()
    emb = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    body = total - emb
    if cfg.is_moe:
        nmat = 3 if cfg.act == "swiglu" else 2
        expert = cfg.n_layers * cfg.n_experts * nmat * cfg.d_model * cfg.d_ff
        body = body - expert + expert * cfg.moe_top_k / cfg.n_experts
    return int(body)


def model_flops(cfg, shape) -> float:
    na = n_active_params(cfg)
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * na * tokens
    if shape.kind == "prefill":
        return 2.0 * na * tokens
    return 2.0 * na * shape.global_batch  # decode: one token per sequence


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             outdir: str, force: bool = False,
             micro_batches: Optional[int] = None,
             tag_suffix: str = "",
             opts: tuple = ()) -> Optional[Dict]:
    """opts: beyond-paper optimisation toggles for §Perf reruns:
        'embed_dshard'           — lookup table d_model-sharded on 'model'
        'serve_params_resident'  — no FSDP axis on serve-path params
        'ssd_chunk128'           — SSD chunk 256 -> 128
    The default (no opts) is the paper-faithful baseline configuration.
    """
    import jax
    from repro.configs import SHAPES, cell_skip_reason, get_arch
    from repro.launch.mesh import (HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16,
                                   TP_SIZE, make_production_mesh)
    from repro.launch.specs import (batch_specs, cache_specs,
                                    decode_token_specs, params_specs,
                                    state_specs)
    from repro.models.model import LM
    from repro.models.sharding import use_mesh
    from repro.optim.optimizer import OptConfig
    from repro.serve.serve_step import make_decode_step, make_prefill_step
    from repro.train.train_step import make_train_step

    mesh_name = "multipod512" if multi_pod else "pod256"
    tag = f"{arch}_{shape_name}_{mesh_name}{tag_suffix}"
    path = os.path.join(outdir, f"{tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg0 = get_arch(arch)
    shape = SHAPES[shape_name]
    if micro_batches is None:
        # 8 microbatches => per-device microbatch 1 (multi-pod) / 2
        # (single-pod): keeps saved residuals of 48L models inside HBM.
        default = 8 if SHAPES[shape_name].kind == "train" else 1
        micro_batches = DEFAULT_MICRO.get((arch, shape_name), default)
    skip = cell_skip_reason(cfg0, shape)
    if skip:
        rec = dict(cell=tag, arch=arch, shape=shape_name, mesh=mesh_name,
                   skipped=skip)
        os.makedirs(outdir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"[dryrun] {tag}: SKIP ({skip})")
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = cfg0.padded_for_mesh(TP_SIZE)
    if "ssd_chunk128" in opts and cfg.ssm_chunk > 128:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, ssm_chunk=128)
    model = LM(cfg)
    serve_fsdp = "serve_params_resident" not in opts

    from repro.models import sharding as _sh
    saved_opts = set(_sh.OPTIMIZATIONS)
    _sh.OPTIMIZATIONS.update(opts)

    with use_mesh(mesh):
        if shape.kind == "train":
            sds_state, _ = state_specs(model, mesh)
            sds_batch = batch_specs(cfg, shape, mesh)
            gspecs = None
            if "grad_shard_accum" in opts:
                from repro.train.train_step import make_train_state_specs
                gspecs = make_train_state_specs(model)["params"]
            gdtype = "bfloat16" if "grad_bf16_sync" in opts else None
            step = make_train_step(model, OptConfig(),
                                   micro_batches=micro_batches,
                                   grad_shard_specs=gspecs,
                                   grad_sync_dtype=gdtype)
            lowered = jax.jit(step, donate_argnums=(0,)).lower(
                sds_state, sds_batch)
        elif shape.kind == "prefill":
            sds_params, _ = params_specs(model, mesh, fsdp=serve_fsdp)
            sds_batch = batch_specs(cfg, shape, mesh)
            caches = cache_specs(model, shape, mesh)
            if cfg.is_encoder:
                fn = lambda p, b: model.encode(p, b)
                lowered = jax.jit(fn).lower(sds_params, sds_batch)
            else:
                from repro.launch.mesh import batch_axes_for
                from jax.sharding import NamedSharding, PartitionSpec as P
                cache_sh = jax.tree.map(lambda s: s.sharding, caches)
                ba = batch_axes_for(shape.global_batch, mesh)
                logit_sh = NamedSharding(mesh, P(ba, "model"))
                fn = make_prefill_step(model)
                lowered = jax.jit(
                    fn, donate_argnums=(2,),
                    out_shardings=(logit_sh, cache_sh)).lower(
                    sds_params, sds_batch["tokens"], caches)
        else:  # decode
            from repro.launch.mesh import batch_axes_for
            from jax.sharding import NamedSharding, PartitionSpec as P
            sds_params, _ = params_specs(model, mesh, fsdp=serve_fsdp)
            caches = cache_specs(model, shape, mesh)
            cache_sh = jax.tree.map(lambda s: s.sharding, caches)
            ba = batch_axes_for(shape.global_batch, mesh)
            tok_sh = NamedSharding(mesh, P(ba, None))
            logit_sh = NamedSharding(mesh, P(ba, "model"))
            tok, pos = decode_token_specs(cfg, shape, mesh)
            fn = make_decode_step(model)
            lowered = jax.jit(
                fn, donate_argnums=(3,),
                out_shardings=(tok_sh, logit_sh, cache_sh)).lower(
                sds_params, tok, pos, caches)
        compiled = lowered.compile()
    _sh.OPTIMIZATIONS.clear()
    _sh.OPTIMIZATIONS.update(saved_opts)

    from repro.launch.hlo_analysis import analyze

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jax wraps the dict in a list
        cost = cost[0] if cost else {}
    hlo = analyze(compiled.as_text())

    chips = 512 if multi_pod else 256
    # trip-count-correct per-device numbers from the HLO analyzer
    # (cost_analysis counts while bodies once — kept for reference only)
    flops = float(hlo["flops"])
    bytes_ = float(hlo["mem_bytes"])
    coll_bytes = float(hlo["collective_bytes"])
    t_compute = flops / PEAK_FLOPS_BF16
    t_memory = bytes_ / HBM_BW
    t_coll = coll_bytes / ICI_BW_PER_LINK
    mf = model_flops(cfg, shape)
    useful = mf / (flops * chips) if flops else 0.0
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]

    rec = dict(
        cell=tag, arch=arch, shape=shape_name, mesh=mesh_name,
        kind=shape.kind, chips=chips, opts=list(opts),
        micro_batches=micro_batches,
        compile_s=round(time.time() - t0, 1),
        hlo_flops_per_device=flops,
        hlo_bytes_per_device=bytes_,
        hlo_bytes_upper_per_device=float(hlo["mem_bytes_upper"]),
        hlo_bytes_dots_per_device=float(hlo.get("mem_bytes_dots", 0.0)),
        collective_bytes_per_device=coll_bytes,
        collectives={**hlo["collective_by_kind"],
                     **{f"n_{k}": v for k, v in
                        hlo["collective_counts"].items()}},
        xla_cost_analysis=dict(
            flops=float(cost.get("flops", 0.0)),
            bytes_accessed=float(cost.get("bytes accessed", 0.0))),
        memory=dict(
            argument_bytes=int(mem.argument_size_in_bytes),
            output_bytes=int(mem.output_size_in_bytes),
            temp_bytes=int(mem.temp_size_in_bytes),
            code_bytes=int(mem.generated_code_size_in_bytes),
            # NOTE: the CPU (host) backend ignores buffer donation, so for
            # decode cells temp double-counts the donated cache (~2x). On
            # the TPU backend input caches alias outputs; subtract
            # output_bytes from temp for the HBM-fit estimate.
            hbm_estimate_bytes=int(mem.argument_size_in_bytes
                                   + max(mem.temp_size_in_bytes
                                         - mem.output_size_in_bytes, 0)),
        ),
        model_flops_global=mf,
        useful_flop_ratio=useful,
        t_compute_s=t_compute,
        t_memory_s=t_memory,
        t_collective_s=t_coll,
        dominant=dominant,
        roofline_fraction=(max(t_compute, 1e-30) /
                           max(t_compute, t_memory, t_coll)),
    )
    os.makedirs(outdir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    print(f"[dryrun] {tag}: ok in {rec['compile_s']}s | "
          f"flops/dev={flops:.3e} bytes/dev={bytes_:.3e} "
          f"coll/dev={coll_bytes:.3e} dominant={dominant} "
          f"temp={rec['memory']['temp_bytes']/2**30:.2f}GiB")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(ARTIFACT_DIR))
    args = ap.parse_args(argv)

    from repro.configs import ARCHS, SHAPES
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                for mp in meshes:
                    cells.append((a, s, mp))
    elif args.arch:
        shapes = [args.shape] if args.shape else list(SHAPES)
        for s in shapes:
            for mp in meshes:
                cells.append((args.arch, s, mp))

    failures = []
    for a, s, mp in cells:
        try:
            run_cell(a, s, mp, args.out, args.force)
        except Exception as e:
            failures.append((a, s, mp, repr(e)))
            print(f"[dryrun] {a}_{s}_{'multi' if mp else 'single'}: "
                  f"FAIL {e!r}")
            traceback.print_exc()
    print(f"[dryrun] done; {len(failures)} failures")
    if failures:
        for f in failures:
            print("  FAIL:", f)
        sys.exit(1)


if __name__ == "__main__":
    main()
