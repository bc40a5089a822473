"""Readings that set a configuration's ``check.limits``.

    python3 bench/control.py --config googlenet-224 --traffic poisson \\
        --seeds 1-12 --control-seeds 3

For every seed: the benchmark's weights and image pool, the plain
reference at the matmul precision the configuration states and at
``highest``, and the served path's logits, with every image of the pool
sent through every bucket of the traffic's ladder. The readings are the
numbers a run may compare (``harness.readings``). For the first
``--control-seeds`` seeds, the controls put in the program's place: the
reference in ``bfloat16`` (one step below the stated float32), in
``int8``, and in bfloat16 with float32 logits (``bench/reference.py``).

Each seed's readings are one JSON line on standard output. Needs a TPU.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def seeds(text: str):
    if "-" in text:
        lo, hi = text.split("-")
        return list(range(int(lo), int(hi) + 1))
    return [int(s) for s in text.split(",")]


def served(engine, pool, buckets):
    """Every image of the pool sent through every bucket; the answers as a
    ``loadgen.Window``."""
    from repro.serving.cnn_engine import CNNRequest
    from bench import loadgen
    win = loadgen.Window("control", 0.0)
    rid = 0
    for b in buckets:
        for lo in range(0, len(pool), b):
            for j in range(b):
                i = (lo + j) % len(pool)
                engine.submit(CNNRequest(rid=rid, image=pool[i]))
                win.image[rid] = i
                rid += 1
            engine.step()
            for r in list(engine.done):
                win.logits[r] = engine.done.pop(r)
    return win


def read_seed(graph, plan, traffic, check, seed, chips, engine=None,
              controls=True):
    """One seed's readings; returns them and the engine, which the next
    seed reuses with its own weights."""
    import jax.numpy as jnp
    from bench import reference, system
    from bench.harness import readings, served_logits
    params = system.make_params(graph, seed)
    pool = system.make_images(graph, seed, int(traffic["pool_images"]))
    stated = reference.logits(graph, params, pool,
                              check["reference_precision"])
    highest = reference.logits(graph, params, pool, "highest")
    if engine is None:
        engine = system.make_engine(graph, params, plan, traffic["engine"],
                                    chips)
    else:
        engine.params = params
    win = served(engine, pool, engine.buckets)
    row = {"seed": seed,
           "program": readings(*served_logits(win, stated)),
           "program_vs_highest": readings(
               *served_logits(win, highest))["logit_err"]}
    if controls:
        lows = {"bf16": reference.logits(graph, params, pool,
                                         dtype=jnp.bfloat16),
                "int8": reference.logits(graph, params, pool, int8=True),
                "bf16_f32_logits": reference.logits(
                    graph, params, pool, dtype=jnp.bfloat16,
                    f32_logits=True)}
        for low, out in lows.items():
            row[low] = readings(out, stated)
            row[f"{low}_vs_highest"] = readings(out, highest)["logit_err"]
    return row, engine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--seeds", type=seeds, default=seeds("1-12"))
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    from bench import harness, system
    harness.check_devices(args.chips,
                          harness.load_json(harness.BENCH / "peaks.json"))
    harness.enable_cache()
    config = harness.load_json(harness.BENCH / "configs"
                               / f"{args.config}.json")
    traffic = harness.load_json(harness.BENCH / "traffic"
                                / f"{args.traffic}.json")
    graph = system.build_graph(config)
    plan = system.plan(graph)
    engine = None
    for k, seed in enumerate(args.seeds):
        row, engine = read_seed(graph, plan, traffic, config["check"], seed,
                                args.chips, engine,
                                controls=k < args.control_seeds)
        row["config"] = args.config
        row["limits"] = config["check"]["limits"]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
