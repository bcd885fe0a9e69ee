"""One run of one cell:

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints as the last line of standard output the one JSON object the
benchmark's contract fixes; everything else goes on earlier lines or on
standard error. Exits 2, with no result line, without a TPU the peak table
knows or for an unknown cell.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse     # noqa: E402
import importlib   # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402


def per_layer_values(manifest, cell_name: str, context: dict) -> dict:
    """Each of the cell's per-layer metrics from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in manifest.metrics_for(cell_name, "per_layer"):
        fn, args = manifest.reader(metric["name"])
        value = fn(context, **args)
        if value is not None:
            out[metric["name"]] = value
    return out


def with_units(manifest, values: dict) -> dict:
    units = {m["name"]: m["unit"] for group in ("end_to_end", "per_layer")
             for m in manifest.doc[group]}
    return {k: {"value": v, "unit": units[k]} for k, v in values.items()}


def main(argv=None, manifest=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.harness import common
    try:
        manifest = manifest or common.Manifest()
        cell = manifest.cell(args.workload)
        traffic = manifest.traffic(cell["traffic"])
        config_doc = manifest.config(cell["config"])
        model = manifest.model(config_doc)
        limits = manifest.limits(cell["name"])["limits"]
        # The compile cache before anything touches the backend: where
        # JAX_COMPILATION_CACHE_DIR is set nothing is set in code, else the
        # checkout's fixed .jax_cache.
        from sharetrade_tpu.utils.runtime_env import configure_compile_cache
        configure_compile_cache()
        device = common.require_chip(cell["chips"])
    except common.Refused as exc:
        print(f"chipbench: refused: {exc}", file=sys.stderr)
        return 2
    from chipbench.harness.peaks import peaks_for
    cfg = common.build_config(config_doc, traffic, args.seed)
    out_dir = common.fresh_cwd(cell["name"])
    driver = importlib.import_module(
        "chipbench.harness." + traffic["kind"] + "_window")
    result, compared = driver.run(
        cfg, traffic, limits, model=model, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_start=T_START,
        out_dir=out_dir, device=device, peaks=peaks_for(device["kind"]),
        readers=lambda ctx: per_layer_values(
            manifest, cell["name"],
            dict(ctx, model=model, chips=cell["chips"])))
    wanted = {m["name"] for m in manifest.metrics_for(
        cell["name"], "per_layer" if args.trace else "end_to_end")}
    result["metrics"] = with_units(
        manifest, {k: v for k, v in result["metrics"].items() if k in wanted})
    os.chdir(common.ROOT)
    common.emit_result(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
