"""The reference's training steps, and the numbers ``correct`` compares.

:func:`follow` drives plain float32 steps over the program's first
batches, in blocks of rows whose gradients add up (each block's
activations are recomputed layer by layer), and returns the same three
readings the driver takes from the program:

- ``losses``: the loss of each step;
- ``grad_norms``: per leaf, the norm of the first gradient as the
  optimizer gets it, read back from its first moment after one step
  (``m_1 = (1 - beta1) g_1``);
- ``delta_norms``: per leaf, the norm of the parameters' change over
  the steps followed.

A reference that keeps state no gradient moves says so
(``ref.step_state(cfg, traffic)``: the leaves' ``paths``, a
``block_loss`` that also returns what the rule reads, and the
``update`` of those leaves): :func:`follow` applies it after its
optimizer, every step, and names the leaves under ``state_paths``.

:func:`gaps` reduces a pair of such readings to the numbers compared.
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.reference import common, optim

#: leaves whose first reference gradient is under this share of the
#: median leaf's move by round-off alone under Adam; they are left out
#: of ``delta_gap``
DEAD_GRADIENT_SHARE = 1e-3


def leaf_norms(tree: dict) -> dict:
    return {path: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for path, x in weights.flatten(tree).items()}


def first_gradient_norms(m_tree: dict, beta1: float) -> dict:
    return {p: n / (1.0 - beta1) for p, n in leaf_norms(m_tree).items()}


def delta_norms(params: dict, spec: dict, key) -> dict:
    """Per leaf ``|params - initial|``; the initial value is made again
    from the seed, leaf by leaf, so no second copy of the model is held."""
    flat = weights.flatten(params)
    return {path: jnp.sqrt(jnp.sum(jnp.square(
        flat[path].astype(jnp.float32)
        - weights.leaf(key, i, shape, kind))))
        for i, (path, (shape, kind)) in enumerate(sorted(spec.items()))}


def on_host(losses, grad_norms: dict, deltas: dict) -> dict:
    """The three readings as plain floats."""
    import jax
    losses, grad_norms, deltas = jax.device_get((losses, grad_norms, deltas))
    return {"losses": [float(x) for x in losses],
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in deltas.items()}}


def follow(ref, cfg: dict, spec: dict, seed: int, batches, *, optimizer: str,
           opt_kwargs: dict, block_rows: int, rows: "int | None" = None,
           q=common.identity, devices=None,
           traffic: "dict | None" = None) -> dict:
    """Train ``len(batches)`` plain steps from the seed's weights.

    ``rows`` keeps only the first rows of every batch (a planted fault:
    part of the batch left out, the mean taken over the rest); ``q`` is
    the precision of the matmul operands (the control lowers it).
    With several ``devices`` the rows of a block are spread over them and
    the weights copied to each: the compiler divides the same plain
    program, which is then the faster by that many chips.  ``traffic``
    is the cell's, for a reference whose step keeps state of its own.
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    update = optim.OPTIMIZERS[optimizer]
    kept = ref.step_state(cfg, traffic or {}) \
        if hasattr(ref, "step_state") else None
    block_loss = kept["block_loss"] if kept else (
        lambda *args: (ref.block_loss(*args), None))
    key = weights.seed_key(seed)
    if rows is not None:
        batches = [tuple(a[:rows] for a in b) for b in batches]
    n_rows = batches[0][0].shape[0]
    block_rows = min(block_rows, n_rows)
    if n_rows % block_rows:
        raise ValueError(f"{n_rows} rows do not divide into blocks of "
                         f"{block_rows}")
    devices = list(devices) if devices else [None]
    spread = len(devices) > 1 and block_rows % len(devices) == 0
    mesh = Mesh(devices, ("rows",)) if spread else None
    everywhere = NamedSharding(mesh, P()) if spread else None

    def step(params, ostate, batch):
        totals = ref.totals(batch)
        blocks = jax.tree.map(
            lambda a: a.reshape(n_rows // block_rows, block_rows,
                                *a.shape[1:]), batch)
        if spread:
            blocks = jax.lax.with_sharding_constraint(
                blocks, NamedSharding(mesh, P(None, "rows")))

        def body(carry, block):
            loss, grads = carry
            (l, read), g = jax.value_and_grad(block_loss, has_aux=True)(
                params, block, totals, cfg, q)
            return (loss + l, jax.tree.map(jnp.add, grads, g)), read

        zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))
        (loss, grads), read = jax.lax.scan(body, zero, blocks)
        params, ostate = update(params, grads, ostate, **opt_kwargs)
        if kept:
            # what the rule reads adds up over the blocks of rows
            flat = weights.flatten(params)
            flat.update(kept["update"](
                {p: flat[p] for p in kept["paths"]},
                jax.tree.map(lambda a: jnp.sum(a, axis=0), read)))
            params = weights.nest(flat)
        return params, ostate, loss

    beta1 = opt_kwargs.get("betas", (0.9, 0.999))[0]
    with jax.default_matmul_precision("highest"), \
            jax.default_device(devices[0]):
        params = jax.jit(lambda k: weights.make(spec, k),
                         out_shardings=everywhere)(key)
        ostate = jax.jit(optim.init)(params)
        jstep = jax.jit(step, donate_argnums=(0, 1))
        losses, grad_norms = [], None
        for n, batch in enumerate(batches):
            params, ostate, loss = jstep(params, ostate,
                                         tuple(jnp.asarray(a) for a in batch))
            losses.append(loss)
            if n == 0:
                grad_norms = jax.jit(first_gradient_norms, static_argnums=1)(
                    ostate["m"], beta1)
        deltas = jax.jit(lambda p, k: delta_norms(p, spec, k))(params, key)
        out = on_host(losses, grad_norms, deltas)
    del params, ostate
    out["state_paths"] = list(kept["paths"]) if kept else []
    return out


def _worst(got: dict, want: dict, paths) -> "tuple[float, str]":
    """The worst leaf's gap of norms: ``|got - want|`` over the larger of
    the reference's norm of that leaf and of the median leaf."""
    paths = list(paths)
    if not paths:
        return 0.0, ""
    floor = statistics.median(want[p] for p in paths)
    gap, path = max(
        (abs(got[p] - want[p]) / max(want[p], floor, 1e-30), p)
        for p in paths)
    return gap, path


def _median(got: dict, want: dict, paths) -> float:
    """The median leaf's gap of norms, measured as :func:`_worst`
    measures the worst leaf's."""
    floor = statistics.median(want[p] for p in paths)
    return statistics.median(
        abs(got[p] - want[p]) / max(want[p], floor, 1e-30) for p in paths)


def gaps(got: dict, want: dict) -> dict:
    """The numbers compared, from the program's readings (or a stand-in's)
    and the reference's.  Each is a share of the reference's value."""
    if len(got["losses"]) != len(want["losses"]) or \
            set(got["grad_norms"]) != set(want["grad_norms"]):
        raise ValueError("the two readings are not of the same steps and "
                         "leaves")
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], want["losses"]))
    paths = sorted(want["grad_norms"])
    grad_gap, grad_leaf = _worst(got["grad_norms"], want["grad_norms"], paths)
    median = statistics.median(want["grad_norms"].values())
    live = [p for p in paths
            if want["grad_norms"][p] >= DEAD_GRADIENT_SHARE * median]
    delta_gap, delta_leaf = _worst(got["delta_norms"], want["delta_norms"],
                                   live)
    # the worst leaf of an expert model is a router's or an expert's, set
    # by the few (token, expert) pairs that rounding moves; the median
    # leaf's gap is steady from seed to seed and reads the precision
    out = {"loss_gap": loss_gap, "grad_gap": grad_gap,
           "grad_gap_median": _median(got["grad_norms"], want["grad_norms"],
                                      paths),
           "delta_gap": delta_gap,
           "worst_leaves": {"grad_gap": grad_leaf, "delta_gap": delta_leaf},
           "leaves_left_out": len(paths) - len(live)}
    kept = want.get("state_paths")
    if kept:
        # leaves that a rule of the step's own moves, not a gradient:
        # each against the reference's own change of it, no median floor
        out["state_gap"], out["worst_leaves"]["state_gap"] = max(
            (abs(got["delta_norms"][p] - want["delta_norms"][p])
             / max(want["delta_norms"][p], 1e-30), p) for p in kept)
    return out
