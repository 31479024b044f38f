import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax-importing module: jax locks the device count on
# first init. 512 placeholder host devices let jax.make_mesh build the
# production meshes; nothing is allocated (inputs are ShapeDtypeStructs).

"""Multi-pod dry-run: lower + compile EVERY (arch × shape × mesh) cell.

(No ``from __future__ import annotations`` here: the XLA_FLAGS lines above
must stay the first statements of the module.)

For each cell this prints ``compiled.memory_analysis()`` (proves the program
fits / records honest bytes-per-device) and ``compiled.cost_analysis()``,
runs the trip-count-aware HLO cost walk (launch/hlocost.py), derives the
three roofline terms, and appends a JSON record under
``results/dryrun/<mesh>/<arch>__<shape>.json`` (resumable; failures recorded
with tracebacks — a sharding mismatch here is a bug in the system).

Usage:
    python -m repro.launch.dryrun --arch gemma-7b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --skip-existing
"""

import argparse
import gzip
import json
import time
import traceback

import jax
import numpy as np

from repro.configs import (SHAPES, all_archs, applicable_shapes, get_config)
from repro.configs.base import ModelConfig, OptimizerConfig, ShapeConfig
from repro.launch import mesh as mesh_lib
from repro.launch.hlocost import hlo_cost
from repro.models.registry import get_model
from repro.training import lower_cell

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "results", "dryrun")


def model_param_counts(config: ModelConfig) -> tuple[int, int]:
    """(total, active-per-token) parameter counts."""
    model = get_model(config)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), config))
    total = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(shapes))
    active = total
    if config.num_experts > 0:
        from repro.models.moe import padded_experts
        per_expert = config.d_model * config.d_ff * (3 if config.mlp_gated
                                                     else 2)
        # padded experts (a2a EP) contribute memory but no active compute
        expert_total_padded = (config.num_layers * padded_experts(config)
                               * per_expert)
        expert_active = (config.num_layers * config.experts_per_token
                         * per_expert)
        active = total - expert_total_padded + expert_active
    return total, active


def model_flops(config: ModelConfig, shape: ShapeConfig) -> float:
    """Analytical 'useful' FLOPs per step (the 6·N·D yardstick + attention)."""
    _, n_active = model_param_counts(config)
    B, S = shape.global_batch, shape.seq_len
    hd = config.resolved_head_dim
    h = config.num_heads
    if shape.kind == "train":
        tokens = B * S
        base = 6.0 * n_active * tokens
        if config.family in ("dense", "moe", "vlm", "audio"):
            n_attn = config.num_layers + config.encoder_layers
            base += 6.0 * B * S * S * h * hd * n_attn / 2  # causal half
        elif config.family == "hybrid":
            n_attn = sum(k == "attn" for k in
                         __import__("repro.models.rglru",
                                    fromlist=["layer_kinds"]).layer_kinds(config))
            w = min(config.local_window, S)
            base += 6.0 * B * S * w * h * hd * n_attn
        return base
    if shape.kind == "prefill":
        tokens = B * S
        base = 2.0 * n_active * tokens
        if config.family in ("dense", "moe", "vlm", "audio"):
            n_attn = config.num_layers + config.encoder_layers
            base += 2.0 * B * S * S * h * hd * n_attn / 2
        elif config.family == "hybrid":
            n_attn = sum(k == "attn" for k in
                         __import__("repro.models.rglru",
                                    fromlist=["layer_kinds"]).layer_kinds(config))
            base += 2.0 * B * S * min(config.local_window, S) * h * hd * n_attn
        return base
    # decode: one token, full cache read
    base = 2.0 * n_active * B
    if config.family in ("dense", "moe", "vlm", "audio"):
        base += 4.0 * B * S * h * hd * config.num_layers
    elif config.family == "hybrid":
        n_attn = sum(k == "attn" for k in
                     __import__("repro.models.rglru",
                                fromlist=["layer_kinds"]).layer_kinds(config))
        base += 4.0 * B * min(config.local_window, S) * h * hd * n_attn
    elif config.family == "ssm":
        base += 4.0 * B * config.num_layers * config.num_heads * hd * hd
    return base


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             outdir: str, save_hlo: bool = False,
             overrides: dict | None = None, tag: str = "") -> dict:
    config = get_config(arch)
    trainer = compression = None
    opt = None
    if overrides:
        overrides = dict(overrides)
        trainer = overrides.pop("_trainer", None)
        compression = overrides.pop("_compression", None)
        opt_kw = overrides.pop("_opt", None)
        if opt_kw:
            opt = OptimizerConfig(**opt_kw)
        if overrides:
            config = config.replace(**overrides)
    shape = SHAPES[shape_name]
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "chips": n_chips, "tag": tag, "ok": False}
    t0 = time.time()
    try:
        if trainer == "dp":
            from repro.parallel.dp import lower_dp_cell
            lowered = lower_dp_cell(config, shape, mesh, opt=opt,
                                    compression=compression)
        else:
            lowered, kind = lower_cell(config, shape, mesh, opt=opt)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        ma = compiled.memory_analysis()
        print(f"--- {arch} × {shape_name} × {rec['mesh']} memory_analysis:")
        print(f"    args={ma.argument_size_in_bytes/2**30:.3f}GiB "
              f"out={ma.output_size_in_bytes/2**30:.3f}GiB "
              f"temp={ma.temp_size_in_bytes/2**30:.3f}GiB "
              f"peak={ma.peak_memory_in_bytes/2**30:.3f}GiB per device")
        ca = compiled.cost_analysis()
        print(f"    cost_analysis: flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e} (body-once, see walker)")
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes": ma.peak_memory_in_bytes,
        }
        rec["xla_cost"] = {"flops": ca.get("flops", 0.0),
                           "bytes": ca.get("bytes accessed", 0.0)}
        t2 = time.time()
        txt = compiled.as_text()
        cost = hlo_cost(txt, pod_size=256 if multi_pod else 0)
        rec["walk_s"] = round(time.time() - t2, 1)
        rec["hlo_cost"] = cost
        # roofline terms (per-chip costs; see EXPERIMENTS.md §Roofline)
        mf = model_flops(config, shape)
        n_total, n_active = model_param_counts(config)
        compute_s = cost["flops"] / mesh_lib.PEAK_FLOPS_BF16
        memory_s = cost["bytes"] / mesh_lib.HBM_BW
        coll_s = cost["ici_bytes"] / mesh_lib.ICI_BW
        dcn_s = cost["dcn_bytes"] / mesh_lib.DCN_BW
        dominant = max((("compute", compute_s), ("memory", memory_s),
                        ("collective", coll_s + dcn_s)), key=lambda kv: kv[1])
        rec["roofline"] = {
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": coll_s, "dcn_s": dcn_s,
            "dominant": dominant[0],
            "model_flops": mf,
            "model_flops_per_chip": mf / n_chips,
            "useful_ratio": (mf / n_chips) / max(cost["flops"], 1.0),
            "params_total": n_total, "params_active": n_active,
        }
        rec["ok"] = True
        if save_hlo:
            with gzip.open(os.path.join(
                    outdir, f"{arch}__{shape_name}{tag}.hlo.txt.gz"),
                    "wt") as f:
                f.write(txt)
        print(f"    roofline: compute={compute_s*1e3:.2f}ms "
              f"memory={memory_s*1e3:.2f}ms ici={coll_s*1e3:.2f}ms "
              f"dcn={dcn_s*1e3:.2f}ms dominant={dominant[0]} "
              f"useful={rec['roofline']['useful_ratio']:.2f}")
    except Exception:
        rec["error"] = traceback.format_exc()
        print(f"!!! {arch} × {shape_name} FAILED:\n{rec['error']}")
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, f"{arch}__{shape_name}{tag}.json"),
              "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--override", default=None,
                    help="JSON dict of ModelConfig overrides (perf iters)")
    ap.add_argument("--tag", default="",
                    help="suffix for the result JSON (perf iters)")
    args = ap.parse_args()
    overrides = json.loads(args.override) if args.override else None

    cells: list[tuple[str, str]] = []
    if args.all:
        for arch in all_archs():
            for sh in applicable_shapes(get_config(arch)):
                cells.append((arch, sh))
        # cheap cells first so results stream in
        def cost_key(cell):
            cfg = get_config(cell[0])
            return (cfg.num_layers * cfg.d_model * cfg.d_model
                    * (3 if cell[1] == "train_4k" else 1))
        cells.sort(key=cost_key)
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    base_out = args.out or os.path.normpath(RESULTS)
    n_ok = n_fail = n_skip = 0
    for multi in meshes:
        outdir = os.path.join(base_out, "multi" if multi else "single")
        for arch, sh in cells:
            path = os.path.join(outdir, f"{arch}__{sh}.json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("ok"):
                        n_skip += 1
                        continue
            rec = run_cell(arch, sh, multi, outdir, save_hlo=args.save_hlo,
                           overrides=overrides, tag=args.tag)
            n_ok += rec["ok"]
            n_fail += not rec["ok"]
    print(f"dry-run done: ok={n_ok} fail={n_fail} skipped={n_skip}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
