"""Drive a whole benchmark run on the CPU at a tiny size, with or without
a fault planted in the timed path underneath."""
import time

import jax.numpy as jnp

from bench import harness

TINY_ARGS = {
    "googlenet-224": {"res": 32, "scale": 0.125, "classes": 10},
    "inception_v4-299": {"res": 75, "scale": 0.125, "classes": 10},
}


def tiny_config(name: str) -> dict:
    config = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    config = dict(config, args=dict(config["args"], **TINY_ARGS[name]))
    config.pop("expect")
    return config


def altered_answers(monkeypatch) -> None:
    """Every compiled bucket program returns its first row's logits moved
    by 3% of their largest magnitude: one answer altered where it is
    produced."""
    from repro.serving import cnn_engine
    real = cnn_engine.compile_plan

    def compile_plan(*args, **kw):
        run = real(*args, **kw)

        def altered(params, x):
            out = run(params, x)
            return out.at[0].add(0.03 * jnp.max(jnp.abs(out[0])))
        return altered

    monkeypatch.setattr(cnn_engine, "compile_plan", compile_plan)


def bf16_control_in_place(monkeypatch) -> None:
    """Every compiled bucket program is the plain reference computed in
    bfloat16, one step below the stated float32: the control, put in the
    program's place."""
    import jax
    from bench import reference
    from repro.serving import cnn_engine

    def compile_plan(graph, *args, **kw):
        @jax.jit
        def control(params, x):
            low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
            return reference.forward(graph, low, x.astype(jnp.bfloat16))
        # Widened outside the jitted program, whose compiler may otherwise
        # drop a bfloat16 round trip as excess precision.
        return lambda params, x: control(params, x).astype(jnp.float32)

    monkeypatch.setattr(cnn_engine, "compile_plan", compile_plan)


def run_cell(monkeypatch, cell: str, seconds: float = 0.5) -> dict:
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    config = tiny_config(harness.find_cell(spec, cell)["config"])
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    return harness.run(cell, 2**33 + 11, seconds, False,
                       t_start=time.monotonic(), spec=spec, config=config,
                       require_tpu=False)
