"""Readings the limits of ``correct`` are set from (PERF.md, section 2):

    python -m chipbench.tools.calibrate --workload <cell> --seeds 1,2,3 \
        --what program,control,faults [--seconds 6]

``program``: the numbers a sound run of the program reads against the plain
reference (the lower reading is their largest). ``control``: the reference
computed in int8 put in the program's place (the upper reading is its
smallest). ``faults`` (training cells): the reference with a fault planted
put in the program's place. One JSON line per reading. Not run by the
benchmark's own runs.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys


def _detail(side, ref):
    """Raw losses and the three widest leaves of each norm, for the look
    PERF.md asks for where a number reads high."""
    import statistics

    import numpy as np

    from chipbench.harness import correct

    def plain(d):
        return {k: v for k, v in d.items() if k not in ("cache", "shares")}

    out = {"losses": side["losses"], "ref_losses": ref["losses"],
           "side": plain(side), "ref": plain(ref),
           "kv_err_by_layer": {
               name: by_layer.tolist() for name, by_layer in
               correct.cache_errors(side["cache"], ref["cache"]).items()},
           "shares_mean": [float(np.mean(np.abs(side["shares"]))),
                           float(np.mean(np.abs(ref["shares"])))],
           "shares_same": float(np.mean(side["shares"] == ref["shares"]))}
    for key in ("grad", "change"):
        floor = statistics.median(ref[key].values())
        gaps = sorted(((abs(side[key][n] - r) / max(r, floor), n,
                        side[key][n], r) for n, r in ref[key].items()),
                      reverse=True)[:3]
        out[key + "_widest"] = gaps
    return out


def bf16_round(x):
    """A second witness: the reference with the operands of every dense
    product rounded to bfloat16, the precision the configuration states."""
    import jax
    import jax.numpy as jnp
    q = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


def train_readings(model, cfg, traffic, seed, what):
    from sharetrade_tpu.runtime.orchestrator import Orchestrator
    from chipbench.harness import common, correct, flops, reference
    from chipbench.harness import train_window as tw
    sizes = flops.sizes(cfg, model)
    prices = common.make_prices(traffic["prices"])

    def reference_side(**kwargs):
        return tw.reference_training(
            model, sizes, cfg.learner, prices, seed,
            initial_budget=cfg.env.initial_budget, **kwargs)

    ref = reference_side()

    def reading(side):
        return dict(correct.training_numbers(side, ref, model),
                    detail=_detail(side, ref))

    if "program" in what:
        orc = Orchestrator(cfg)
        orc.send_training_data(prices)
        program = tw.drive_first_steps(orc, model)
        orc.stop()
        orc._ts = None
        del orc
        gc.collect()
        yield "program", reading(program)
    if "bf16" in what:
        yield "bf16_reference", reading(reference_side(quant=bf16_round))
    if "control" in what:
        yield "control", reading(reference_side(quant=reference.int8_quant))
    if "faults" in what:
        for fault in ("half_batch", "token", "token16", "unchanged"):
            yield "fault:" + fault, reading(reference_side(fault=fault))


def serve_readings(model, cfg, traffic, seed, what, seconds):
    from chipbench.harness import flops, reference
    from chipbench.harness import serve_window as sw
    sizes = flops.sizes(cfg, model)
    sessions = sw.serve_sessions(cfg, traffic, seed, seconds)
    sample = sw.draw_sample(sessions, seed, traffic["load"]["check_sessions"])
    if "program" in what:
        yield "program", sw.serving_numbers(sample, seed, model, sizes)
    if "control" in what:
        yield "control", sw.serving_numbers(sample, seed, model, sizes,
                                            quant=reference.int8_quant)
    if "faults" in what:
        yield "fault:answer", sw.serving_numbers(sample, seed, model, sizes,
                                                 alter=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.tools.calibrate")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control,faults")
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    from chipbench.harness import common
    cell, config_doc, traffic, model, device = common.open_cell(
        args.workload, ".calibrate")
    what = args.what.split(",")
    for seed in (int(s) for s in args.seeds.split(",")):
        cfg = common.build_config(config_doc, traffic, seed)
        if traffic["kind"] == "train":
            readings = train_readings(model, cfg, traffic, seed, what)
        else:
            readings = serve_readings(model, cfg, traffic, seed, what,
                                      args.seconds)
        for name, numbers in readings:
            print(json.dumps({"cell": cell["name"], "seed": seed,
                              "reading": name, "numbers": numbers,
                              "device": device["kind"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
