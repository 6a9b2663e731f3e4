"""The training pod's program as the benchmark starts it: what follows
``python -m paddle_operator_tpu.launch.launcher --``.

Builds one object, the compiled step with its state (the program's own
``make_train_step`` on the program's own model, optimizer and shardings;
the weights are the benchmark's, from ``--seed``), drives it through its
first steps with ``train/trainer.py fit`` on token ids fed by
``train/data.py``'s ``DevicePrefetcher``, reads what the comparison needs
from its state, and hands that same object to the window.
"""

from __future__ import annotations

import json
import os
import sys
import time


def first_grad_norms(opt_state, b1: float) -> dict:
    """Per leaf, the norm of the first gradient as the optimizer got it,
    worked out from the first moment after one step (``mu = (1 - b1) g``)
    as ``train/opt8bit.py`` stores it: int8 codes times per-block scales."""
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(opt_state)
    parts: dict = {}
    for path, leaf in flat:
        keys = [str(getattr(p, "name", getattr(p, "key", getattr(p, "idx", p))))
                for p in path]
        if "mu" not in keys or keys[-1] not in ("q8_codes", "q8_scale"):
            continue
        name = "/".join(keys[keys.index("mu") + 1:-1])
        parts.setdefault(name, {})[keys[-1]] = leaf

    @jax.jit
    def norms(parts):
        return {n: jnp.sqrt(jnp.sum(
            (p["q8_codes"].astype(jnp.float32) * p["q8_scale"]) ** 2))
            / (1 - b1) for n, p in parts.items()}

    return {n: float(x) for n, x in norms(parts).items()}


def change_norms(params, seed_key) -> dict:
    """Per leaf, the norm of (parameters now - parameters at the start), the
    start made again from the seed."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import weights as W

    @jax.jit
    def norms(seed_key, params):
        def one(path, a):
            name = W.path_name(path)
            if name.startswith("layers/"):
                def sq(l):
                    d = a[l].astype(jnp.float32) - W.make_leaf(
                        seed_key, name, a.shape[1:], a.dtype, l).astype(
                            jnp.float32)
                    return jnp.sum(d * d)
                return jnp.sqrt(jnp.sum(jax.lax.map(
                    sq, jnp.arange(a.shape[0]))))
            d = a.astype(jnp.float32) - W.make_leaf(
                seed_key, name, a.shape, a.dtype).astype(jnp.float32)
            return jnp.sqrt(jnp.sum(d * d))
        return jax.tree_util.tree_map_with_path(one, params)

    flat, _ = jax.tree_util.tree_flatten_with_path(norms(seed_key, params))
    return {W.path_name(p): float(x) for p, x in flat}


class Deadline:
    """``fit``'s drain hook: once the window's seconds have passed the step
    in flight finishes and the loop returns (``fit`` then blocks until the
    parameters are ready)."""

    reason = "window closed"

    def __init__(self) -> None:
        self.at = float("inf")

    @property
    def draining(self) -> bool:
        return time.time() >= self.at


def program(spec: dict, break_step=None) -> dict:
    """The program's side of one run.  `break_step` (tests only) wraps the
    compiled step, to plant a fault underneath the timed path."""
    from paddle_operator_tpu.launch import launcher
    from paddle_operator_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    env = launcher.initialize()
    import jax
    import jax.numpy as jnp

    from benchmark.harness import jaxenv
    from benchmark.harness import traffic as TR
    from benchmark.harness import weights as W

    compiles = jaxenv.count_compiles()
    device = jaxenv.device_report()
    cfg, traffic = spec["config"], spec["traffic"]
    if device["platform"] != cfg["platform"] or \
            device["count"] < spec["chips"]:
        return {"device": device, "early": True}

    from paddle_operator_tpu.models import llama as L
    from paddle_operator_tpu.train import trainer as T
    from paddle_operator_tpu.train.data import DevicePrefetcher

    seed, batch, seq = spec["seed"], traffic["batch"], traffic["seq"]
    lcfg = jaxenv.llama_config(cfg, seq, remat=cfg["train"]["remat"],
                               remat_policy=cfg["train"]["remat_policy"])
    mesh = launcher.job_mesh(env)
    model = L.Llama(lcfg, mesh)
    o = cfg["train"]["optimizer"]
    opt = T.make_optimizer(o["learning_rate"], warmup_steps=o["warmup_steps"],
                           decay_steps=o["decay_steps"],
                           weight_decay=o["weight_decay"],
                           grad_clip=o["grad_clip"], moments=o["moments"])
    example = (jnp.zeros((batch, 8), jnp.int32),)
    shardings, _ = T.state_shardings(model, opt, mesh,
                                     L.partition_patterns(lcfg), example)
    shapes = jax.eval_shape(
        lambda r: model.init(r, *example)["params"], jax.random.PRNGKey(0))
    seed_key = W.root_key(seed)

    def init_fn(key):
        params = W.make_tree(key, shapes)
        return T.TrainState(step=jnp.zeros((), jnp.int32), params=params,
                            opt_state=opt.init(params))

    with mesh:
        state = jax.jit(init_fn, out_shardings=shardings)(seed_key)
    step = T.make_train_step(model, opt, mesh, shardings)

    waits: list = []

    def timed(it):
        while True:
            t0 = time.perf_counter()
            b = next(it)
            waits.append(time.perf_counter() - t0)
            yield b

    data = timed(DevicePrefetcher(
        TR.train_batches(seed, traffic, cfg["vocab_size"]), mesh))

    in_flight: list = []
    tracing = {"n": 0, "dir": spec.get("trace_dir"), "at": (4, 6),
               "window": None}

    def paced(state, batch):
        """The compiled step, with at most two steps in flight; the traced
        run traces whole steps `at[0]`..`at[1]` of the window."""
        tracing["n"] += 1
        n, (a, b) = tracing["n"], tracing["at"]
        if tracing["dir"] and n == a:
            jax.block_until_ready(in_flight)
            jaxenv.start_trace(tracing["dir"])
            tracing["window"] = [time.time(), None]
        out = (break_step or (lambda f: f))(step)(state, batch)
        if tracing["dir"] and n == b:
            jax.block_until_ready(out[1]["loss"])
            jax.profiler.stop_trace()
            tracing["window"][1] = time.time()
            tracing["dir"] = None
        if in_flight:
            jax.block_until_ready(in_flight.pop())
        in_flight.append(out[1]["loss"])
        return out

    # ---- set-up: the first steps, one `fit` each, read as they go ----------
    losses, grad_norms = [], None
    trace_dir, tracing["dir"] = tracing["dir"], None
    for k in range(cfg["check"]["steps"]):
        state, h = T.fit(state, paced, data, steps=1)
        losses.append(h[0]["loss"])
        if k == 0:
            grad_norms = first_grad_norms(state.opt_state, 0.9)
    changes = change_norms(state.params, seed_key)
    compiles_setup = dict(compiles)

    # ---- the window ---------------------------------------------------------
    deadline = Deadline()
    tracing.update(n=0, dir=trace_dir)
    waits.clear()
    in_flight_before = list(in_flight)
    jax.block_until_ready(in_flight_before)
    t_open = time.time()
    deadline.at = t_open + spec["seconds"]
    state, hist = T.fit(state, paced, data, steps=10 ** 9,
                        preemption=deadline)
    jax.block_until_ready(state.params)
    t_close = time.time()
    peak = jaxenv.peak_bytes()
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    return {
        "device": device, "t_open": t_open, "t_close": t_close,
        "steps": len(hist), "tokens_per_step": batch * seq,
        "data_wait_s": list(waits), "losses": losses,
        "grad_norms": grad_norms, "change_norms": changes,
        "window_losses": [h["loss"] for h in hist][:8],
        "traced": tracing["window"], "peak_bytes": peak,
        "bytes_in_use": in_use,
        "compiles_setup": [compiles_setup["requests"], compiles_setup["hits"]],
        "compiles_in_window": compiles["requests"] - compiles_setup["requests"],
    }


def main() -> int:
    from benchmark.harness import common as C

    spec = json.load(open(sys.argv[1]))
    out = program(spec)
    C.emit_child_result(out)
    sys.stdout.flush()
    # leave without tearing the runtime down leaf by leaf
    os._exit(3 if out.get("early") else 0)


if __name__ == "__main__":
    sys.exit(main())
