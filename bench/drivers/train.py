"""Training cells: the program's jitted train step on a mesh, fed a ring
of seeded batches.

Set-up builds the step once (``make_jitted_step``), makes the float32
weights on the device from the seed, and drives that same step through
its first three steps on the ring's first three batches; those steps
compile it and are what the reference follows. The window then calls
the same step on the ring, each step ended by ``block_until_ready``.

Correctness, against the reference of the configuration's architecture
(``bench/reference/<model_type>.py``) redoing the three steps in float32
at full precision on one chip (rows in blocks, gradients summed): each
step's loss; per leaf, the norm of the first gradient as the optimizer
got it (its first moment after step 1 over ``1 - beta1``); per leaf, the
norm of the parameters' change after the three steps.
A gap of norms is taken against the larger of the reference's norm of
that leaf and of the median leaf; leaves whose reference gradient is
under a thousandth of the median leaf's move by round-off alone and are
left out of the change.
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from bench import arch, program, traffic, weights
from bench.common import device_record

N_CHECKED = 3  # steps the reference follows


def _opt(cell: dict):
    from repro.train.optimizer import OptConfig

    return OptConfig(**cell["optimizer"])


def lr_at(o: dict, step: int) -> float:
    """The optimizer's learning rate at ``step`` (linear warm-up, then
    cosine decay to ``min_lr_ratio``)."""
    warm = min(step / max(o["warmup_steps"], 1), 1.0)
    frac = min(max((step - o["warmup_steps"]) / max(o["total_steps"] - o["warmup_steps"], 1),
                   0.0), 1.0)
    cos = 0.5 * (1.0 + np.cos(np.pi * frac))
    return o["lr"] * warm * (o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * cos)


def _norms(jax, tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {weights.path_name(p): x for p, x in flat}


def run(ctx) -> tuple[dict, dict, dict]:
    if ctx.control:
        return control(ctx)
    jax = ctx.jax
    jnp = jax.numpy
    from repro.models import build
    from repro.train.train_step import TrainStepConfig, make_jitted_step
    from repro.utils.compat import make_mesh

    cell, cfg, mix = ctx.cell, ctx.config, ctx.mix
    model = build(program.arch_config(cfg, cfg["compute_dtype"]))
    plugin = arch.load(cfg)
    mesh = make_mesh(tuple(cell["mesh"]), ("data", "model"))
    rows = mix["rows_per_chip"] * mesh.shape["data"]
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch_like = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in
                  traffic.train_batch(mix, cfg["vocab_size"], 0, 0, rows).items()}
    opt_cfg = _opt(cell)
    ts = TrainStepConfig(**cell["step"])
    with jax.set_mesh(mesh):
        step, in_sh = make_jitted_step(model, mesh, opt_cfg, ts, tree, batch_like, donate=True)
        from repro.train.optimizer import init_opt_state

        params = weights.make_params(tree, ctx.seed, program.stored_dtype(cfg),
                                     out_shardings=in_sh[0], arch=plugin.module)
        opt_state = jax.jit(lambda p: init_opt_state(opt_cfg, p), out_shardings=in_sh[1])(params)
        ring = [jax.device_put(traffic.train_batch(mix, cfg["vocab_size"], ctx.seed, i, rows),
                               in_sh[2]) for i in range(mix["ring"])]
        leaf_norms = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
        stored = program.stored_dtype(cfg)

        @jax.jit
        def change_norms(p, key):
            flat, treedef = jax.tree_util.tree_flatten_with_path(p)
            out = [jnp.sqrt(jnp.sum(jnp.square(x - weights.leaf(
                key, weights.path_name(q), x.shape, stored(weights.path_name(q)),
                arch=plugin.module)))) for q, x in flat]
            return jax.tree_util.tree_unflatten(treedef, out)

        TA = jax.profiler.TraceAnnotation
        losses, first_grad = [], None
        for i in range(N_CHECKED):
            with TA("bench.train_step"):
                params, opt_state, metrics = step(params, opt_state, ring[i])
                losses.append(float(metrics["loss"]))
            if i == 0:  # the gradient as AdamW got it: its first moment / (1 - beta1)
                first_grad = {k: float(v) / (1.0 - opt_cfg.beta1) for k, v in
                              _norms(jax, leaf_norms(opt_state["m"])).items()}
                grad1 = {k: np.asarray(v) / (1.0 - opt_cfg.beta1)
                         for k, v in _norms(jax, opt_state["m"]).items()}
        changed = {k: float(v) for k, v in
                   _norms(jax, change_norms(params, weights.base_key(ctx.seed))).items()}
        jax.block_until_ready(params)
        setup_s = time.perf_counter() - ctx.t_start

        log = ctx.compile_log
        log.window = 0
        log.in_window = True
        tracer = ctx.tracer
        n, k, traced_steps = 0, N_CHECKED, None
        t0 = time.perf_counter()
        t_end = t0
        if tracer:
            tracer.start()
        while t_end - t0 < ctx.seconds:
            with TA("bench.train_step"):
                params, opt_state, metrics = step(params, opt_state, ring[k % len(ring)])
                loss = metrics["loss"].block_until_ready()
            t_end = time.perf_counter()
            n += 1
            k += 1
            if tracer and tracer.running and t_end - t0 >= ctx.trace_seconds:
                tracer.stop()
                traced_steps = n
        window_s = t_end - t0
        log.in_window = False
        if tracer and tracer.running:
            tracer.stop()
            traced_steps = n
        finite = bool(np.isfinite(float(loss)))
        dev_rec = device_record(jax.devices()[: ctx.chips])
        del params, opt_state, ring, metrics
    gc.collect()

    prog = {"loss": losses, "first_grad": first_grad, "change": changed, "grad1": grad1}
    ref = reference_steps(ctx, rows, "f32")
    checks, readings = compare(cell, prog, ref)
    tokens_per_step = int(mix["rows_per_chip"] * mix["seq"] * round(
        mesh.shape["data"] * (1 - ts.reduce_alpha)))
    result = {"correct": all(c["value"] <= c["limit"] for c in checks.values()) and finite,
              "attempted": n, "failed": 0 if finite else n,
              "metrics": {"train_step_ms": {"value": 1e3 * window_s / n, "unit": "ms"},
                          "setup_s": {"value": setup_s, "unit": "s"}},
              "device": dev_rec}
    record = {"kind": "train", "setup_s": setup_s, "window_s": window_s, "steps": n,
              "traced_steps": traced_steps, "tokens_per_step": tokens_per_step,
              "compiles_in_window": log.window, "window_compiled": log.window_names,
              "compiles_total": log.count, "compile_s": log.seconds,
              "cache_hits": log.cache_hits, "readings": readings,
              "program": {k: v for k, v in prog.items() if k != "grad1"},
              "reference": {k: v for k, v in ref.items() if k != "grad1"}}
    return result, checks, record


def reference_steps(ctx, rows: int, precision: str, fault: str | None = None) -> dict:
    """The reference's three AdamW steps on the compute chips' rows of
    the ring's first three batches: losses, first clipped gradient's
    leaf norms, and the change's leaf norms. ``fault`` plants one of
    the faults a training step can have, for reading its size."""
    jax = ctx.jax
    jnp = jax.numpy
    cell, cfg, mix = ctx.cell, ctx.config, ctx.mix
    o = cell["optimizer"]
    plugin = arch.load(cfg)
    reference = plugin.reference
    stored = program.stored_dtype(cfg)
    shapes = reference.leaf_shapes(cfg)
    key = weights.base_key(ctx.seed)
    W = reference.make_weights(cfg, ctx.seed, stored)
    per_chip = mix["rows_per_chip"]
    compute_rows = per_chip * round(cell["mesh"][0] * (1 - cell["step"]["reduce_alpha"]))
    block = cell["check"]["reference_rows"]
    zeros = jax.jit(lambda: {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()})

    @functools.partial(jax.jit, donate_argnums=(1,))
    def grad_sum(W, acc, tokens, labels, mask):
        """acc + the gradient of the summed loss over these rows."""
        (tot, cnt), g = jax.value_and_grad(
            lambda W: reference.nll_sum(cfg, W, tokens, labels, mask, precision),
            has_aux=True)(W)
        return tot, cnt, jax.tree.map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adamw(W, m, v, g, cnt, t, lr):
        """One AdamW step on the token-mean gradient ``g / cnt``; returns
        the new state and the leaf norms of the clipped gradient."""
        b1, b2 = o["beta1"], o["beta2"]
        g = {k: x / cnt for k, x in g.items()}
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        if o["grad_clip"] > 0:
            g = {k: x * jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(norm, 1e-9))
                 for k, x in g.items()}
        m = {k: b1 * m[k] + (1 - b1) * g[k] for k in g}
        v = {k: b2 * v[k] + (1 - b2) * g[k] * g[k] for k in g}
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        new = {k: (W[k].astype(jnp.float32) - lr * (
            (m[k] / bc1) / (jnp.sqrt(v[k] / bc2) + o["eps"])
            + o["weight_decay"] * W[k].astype(jnp.float32))).astype(W[k].dtype) for k in W}
        if fault == "frozen_leaf":  # one leaf's update never lands
            new["embed/table"] = W["embed/table"]
        return new, m, v, {k: jnp.sqrt(jnp.sum(jnp.square(x))) for k, x in g.items()}

    @jax.jit
    def change_norms(W, key):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            W[k].astype(jnp.float32)
            - weights.leaf(key, k, s, stored(k), arch=plugin.module).astype(jnp.float32))))
            for k, s in shapes.items()}

    m, v = zeros(), zeros()
    losses, first = [], None
    for i in range(N_CHECKED):
        b = traffic.train_batch(mix, cfg["vocab_size"], ctx.seed, i, rows)
        b = {k: x[:compute_rows] for k, x in b.items()}
        if fault == "half_batch":
            b["mask"][compute_rows // 2:] = 0.0
        if fault == "no_exchange":  # each chip updates with its own rows only
            b["mask"][per_chip:] = 0.0
        tot, cnt, acc = 0.0, 0.0, zeros()
        for r in range(0, compute_rows, block):
            t_, c_, acc = grad_sum(W, acc, *(jnp.asarray(b[k][r:r + block])
                                             for k in ("tokens", "labels", "mask")))
            tot, cnt = tot + t_, cnt + c_
        losses.append(float(tot / cnt))
        W, m, v, gn = adamw(W, m, v, acc, cnt, float(i + 1), lr_at(o, i + 1))
        if i == 0:
            first = {k: float(x) for k, x in gn.items()}
            grad1 = {k: np.asarray(x) / (1.0 - o["beta1"]) for k, x in m.items()}
    change = {k: float(x) for k, x in change_norms(W, key).items()}
    del W, m, v, acc
    return {"loss": losses, "first_grad": first, "change": change, "grad1": grad1}


def _gap(a: float, b: float, floor: float) -> float:
    return abs(a - b) / max(abs(b), floor)


def compare(cell: dict, prog: dict, ref: dict) -> tuple[dict, dict]:
    """The compared numbers of a run (``prog``) against the reference."""
    lim = cell["check"]
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
    g_med = float(np.median(list(ref["first_grad"].values())))
    grad = max(_gap(prog["first_grad"][k], ref["first_grad"][k], g_med) for k in ref["first_grad"])
    # the first gradient element by element: the norm of the difference
    diff = max(float(np.linalg.norm(prog["grad1"][k] - ref["grad1"][k]))
               / max(ref["first_grad"][k], g_med) for k in ref["grad1"])
    moved = [k for k in ref["change"] if ref["first_grad"][k] >= 1e-3 * g_med]
    c_med = float(np.median([ref["change"][k] for k in moved]))
    change = max(_gap(prog["change"][k], ref["change"][k], c_med) for k in moved)
    readings = {"loss_gap": loss, "grad_gap": grad, "grad_diff": diff, "change_gap": change,
                "left_out_of_change": sorted(set(ref["change"]) - set(moved))}
    checks = {name: {"value": readings[name], "limit": lim[name]}
              for name in ("loss_gap", "grad_gap", "grad_diff", "change_gap")}
    return checks, readings


def control(ctx) -> tuple[dict, dict, dict]:
    """The control and the planted faults, read against the float32
    reference on one chip: the reference in fp8 in the program's place,
    and the reference with half its batch left out, with each chip's
    gradient kept to its own rows, and with one leaf never updated."""
    jax = ctx.jax
    rows = ctx.mix["rows_per_chip"] * ctx.cell["mesh"][0]
    ref = reference_steps(ctx, rows, "f32")
    readings = {}
    for name, prec, fault in (("control_fp8", "fp8", None), ("half_batch", "f32", "half_batch"),
                              ("no_exchange", "f32", "no_exchange"),
                              ("frozen_leaf", "f32", "frozen_leaf")):
        checks_, readings[name] = compare(ctx.cell, reference_steps(ctx, rows, prec, fault), ref)
        if name == "control_fp8":
            checks = checks_
    dev_rec = device_record(jax.devices()[:1])
    result = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
              "attempted": 0, "failed": 0, "metrics": {}, "device": dev_rec}
    return result, checks, {"kind": "train-control", "readings": readings,
                            "reference": {k: v for k, v in ref.items() if k != "grad1"}}
