"""Port of the reference's largest conformance suite: the mixed-precision
control-flow matrix (``tests/L0/run_amp/test_multiple_models_optimizers_losses.py``,
762 LoC).

Matrix per topology: opt level {O0..O3} x shared/per-loss scalers x injected
inf at a chosen {iteration, tensor-dtype location, backward pass, model},
asserting per-iteration unscaled grads and final params against an
unscaled fp32-reference run (which replays the expected skip pattern).

Mapping notes (SURVEY.md section 7 design stance):

- The reference drives ``with amp.scale_loss(loss_i, optimizer_j, loss_id=k)``
  per backward; each exit unscales into master grads, runs scaler ``k``'s
  ``update_scale``, and arms ``skip_step`` on every optimizer passed
  (``handle.py:110-150``).  Here the same composition is explicit:
  ``Amp.unscale_gradients`` + ``Amp.update_scaler`` + ``Amp.step_if``
  (or ``Amp.apply_gradients_multi`` for the one-optimizer topologies).
- ``how_to_zero`` {none, model, optimizer} has no analog: functional grads
  are fresh by construction, which is the semantics all three spellings
  converge to in the reference.
- The fp16 leaf is bfloat16 here (TPU-native); all test values are small
  dyadic rationals exactly representable in bf16, preserving the reference's
  exact-comparison design.
- ``cast_model_type=False`` (model left at incoming dtypes) maps to
  ``cast_model_dtype=False``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from apex_tpu import amp

HALF = jnp.bfloat16
X = jnp.ones((2,), jnp.float32)
OPT_LEVELS = ("O0", "O1", "O2", "O3")


def make_model(unique):
    """MyModel's params (reference :16-28): an fp32 and a half-dtype leaf."""
    return {
        "w0": unique + jnp.arange(2, dtype=jnp.float32),
        "w1": (1.0 + unique + jnp.arange(2, dtype=jnp.float32)).astype(HALF),
    }


def model_loss(params, x=X):
    """MyModel.ops: ``((x * w0.float()) * w1.float()).sum()``."""
    return ((x * params["w0"].astype(jnp.float32))
            * params["w1"].astype(jnp.float32)).sum()


def sgd_by_group(lr_by_key, momentum):
    """torch.optim.SGD with per-param-group lr: ``buf = m*buf + g;
    p -= lr*buf`` == optax.sgd(lr, momentum=m) routed per top-level key."""
    return optax.multi_transform(
        {k: optax.sgd(lr, momentum=momentum) for k, lr in lr_by_key.items()},
        param_labels=lambda params: {
            k: jax.tree.map(lambda _: k, v) for k, v in params.items()})


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def reference_dtype_params(params, opt_level):
    """Param tree for the fp32-reference run.  Under O2 the amp run carries
    fp32 masters — and, with the model cast disabled, computes on them — so
    its exact reference is an all-fp32 run.  (The torch original compared
    fp32 masters against an fp16-model run and passed only because fp16's
    10 mantissa bits absorb 3 iterations of this arithmetic; bf16's 7 do
    not — SURVEY.md section 7, "bitwise L1 conformance".)  The other levels
    step the incoming mixed-dtype params directly, so the reference keeps
    the bf16 leaf."""
    if opt_level == "O2":
        return jax.tree.map(lambda x: x.astype(jnp.float32), params)
    return params


def tree_allclose(a, b, **kw):
    flat_a, flat_b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(flat_a) == len(flat_b)
    for la, lb in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(la, np.float32),
                                   np.asarray(lb, np.float32), **kw)


def inject_inf_into(grads, model_key, loc):
    """Plant an inf in grads[model_key][w0|w1][0] (reference :139-150:
    ``model.weight{0,1}.grad[0] = inf`` — fp32 -> w0, fp16 -> w1)."""
    leaf = "w0" if loc == "fp32" else "w1"
    g = grads[model_key][leaf]
    grads = dict(grads)
    grads[model_key] = dict(grads[model_key])
    grads[model_key][leaf] = g.at[0].set(jnp.inf)
    return grads


def case_grid(opt_level, which_backwards=(0, 1),
              which_models_by_backward=None):
    """The inject-inf grid of the reference: O1/O2 (dynamic-scaler levels)
    also run with an inf planted at iteration {0,1} x loc x backward
    (x model, when a backward spans several models)."""
    cases = [dict(inject_inf=-1, inject_inf_loc=None, which_backward=None,
                  which_model=None)]
    if opt_level in ("O1", "O2"):
        for inject_inf in (0, 1):
            for loc in ("fp16", "fp32"):
                for wb in which_backwards:
                    models = (which_models_by_backward[wb]
                              if which_models_by_backward else (None,))
                    for wm in models:
                        cases.append(dict(inject_inf=inject_inf,
                                          inject_inf_loc=loc,
                                          which_backward=wb, which_model=wm))
    return cases


def init_amp(opt_level, tx, num_losses):
    a = amp.initialize(optimizer=tx, opt_level=opt_level,
                       cast_model_dtype=False, num_losses=num_losses,
                       half_dtype=HALF, verbosity=0)
    return a


def seed_scales(state, num_losses):
    """The reference pins ``loss_scalers[0]._loss_scale = 4.0`` (and 16.0 for
    a second scaler) so scaled values stay exact (:116-119)."""
    sstates = list(state.scaler_states)
    sstates[0] = sstates[0]._replace(loss_scale=jnp.asarray(4.0, jnp.float32))
    if num_losses == 2:
        sstates[1] = sstates[1]._replace(
            loss_scale=jnp.asarray(16.0, jnp.float32))
    return state._replace(scaler_states=tuple(sstates))


# ---------------------------------------------------------------------------
# topology 1+2: N models, 2 losses, ONE optimizer (reference :45-169,170-325)
# ---------------------------------------------------------------------------

def _run_one_optimizer_case(n_models, opt_level, use_multiple_loss_scalers,
                            case):
    """Shared driver: loss0/loss1 each touch a subset of models; grads
    accumulate into one optimizer through per-loss scalers."""
    if n_models == 2:
        lrs = {"m0": 0.25, "m1": 0.5}
        loss_parts = [("m0",), ("m1",)]          # loss_j = sum of models
    else:
        lrs = {"m0": 0.25, "m1": 0.5, "m2": 0.125}
        loss_parts = [("m0", "m2"), ("m1", "m2")]  # reference :183-186

    def loss_fn(j):
        def f(params):
            return sum(model_loss(params[k]) for k in loss_parts[j])
        return f

    momentum = 0.125
    params0 = {f"m{i}": make_model(1 + i) for i in range(n_models)}

    # ---- fp32 reference run (no amp): 2 iters, grads + final params ----
    tx = sgd_by_group(lrs, momentum)
    ref_params = reference_dtype_params(params0, opt_level)
    ref_opt = tx.init(ref_params)
    reference_grads = []
    for _ in range(2):
        g0 = jax.grad(loss_fn(0))(ref_params)
        g1 = jax.grad(loss_fn(1))(ref_params)
        g = tree_add(g0, g1)
        reference_grads.append(g)
        updates, ref_opt = tx.update(g, ref_opt, ref_params)
        ref_params = optax.apply_updates(ref_params, updates)
    final_params = ref_params

    # ---- amp run ----
    num_losses = 2 if use_multiple_loss_scalers else 1
    loss_ids = [0, 1] if use_multiple_loss_scalers else [0, 0]
    iters = 3 if case["inject_inf"] >= 0 else 2

    a = init_amp(opt_level, sgd_by_group(lrs, momentum), num_losses)
    state = seed_scales(a.init(params0), num_losses)

    unskipped = 0
    for i in range(iters):
        params_c = a.model_params(state)
        grads = []
        for j in (0, 1):
            gj = jax.grad(
                lambda p, j=j: a.scale_loss(
                    a.run(lambda q: loss_fn(j)(q), p),
                    state, loss_id=loss_ids[j]))(params_c)
            if i == case["inject_inf"] and case["which_backward"] == j:
                key = (f"m{case['which_model']}"
                       if case["which_model"] is not None else f"m{j}")
                gj = inject_inf_into(gj, key, case["inject_inf_loc"])
            grads.append(gj)

        if i != case["inject_inf"]:
            combined = None
            for j in (0, 1):
                uj, _ = a.unscale_gradients(state, grads[j],
                                            loss_id=loss_ids[j])
                combined = uj if combined is None else tree_add(combined, uj)
            tree_allclose(combined, reference_grads[unskipped],
                          rtol=1e-6, atol=0)
            unskipped += 1

        state, info = a.apply_gradients_multi(state, grads,
                                              loss_ids=loss_ids)
        assert bool(info["overflow"]) == (i == case["inject_inf"])

    tree_allclose(state.master_params, final_params, rtol=1e-6, atol=0)


# The four-topology matrix sums to ~85s of jit compiles on the 2-vCPU
# tier-1 box (ROADMAP wall-clock item): the smallest topology stays
# tier-1 as the fast representative — it exercises the full opt-level x
# scaler-sharing x inject-inf grid through the same helper the larger
# topologies drive — and the other three are slow-marked.

@pytest.mark.parametrize("use_multiple_loss_scalers", (
    True, pytest.param(False, marks=pytest.mark.slow)))
@pytest.mark.parametrize("opt_level", OPT_LEVELS)
def test_2models2losses1optimizer(opt_level, use_multiple_loss_scalers):
    for case in case_grid(opt_level):
        _run_one_optimizer_case(2, opt_level, use_multiple_loss_scalers, case)


@pytest.mark.slow
@pytest.mark.parametrize("use_multiple_loss_scalers", (True, False))
@pytest.mark.parametrize("opt_level", OPT_LEVELS)
def test_3models2losses1optimizer(opt_level, use_multiple_loss_scalers):
    # which_model: backward 0 spans models {0,2}; backward 1 spans {1,2}
    # (reference :227-233).
    for case in case_grid(opt_level,
                          which_models_by_backward={0: (0, 2), 1: (1, 2)}):
        _run_one_optimizer_case(3, opt_level, use_multiple_loss_scalers, case)


# ---------------------------------------------------------------------------
# topology 3: 2 models, 2 losses, 2 optimizers (reference :326-515)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("use_multiple_loss_scalers", (True, False))
@pytest.mark.parametrize("opt_level", OPT_LEVELS)
def test_2models2losses2optimizers(opt_level, use_multiple_loss_scalers):
    num_losses = 2 if use_multiple_loss_scalers else 1
    loss_ids = [0, 1] if use_multiple_loss_scalers else [0, 0]

    def run_reference(iters, skip, skip_pairs):
        """fp32 run replaying the expected skip pattern
        (what_got_skipped variants, reference :358-404)."""
        p0 = reference_dtype_params({"m0": make_model(1)}, opt_level)
        p1 = reference_dtype_params({"m1": make_model(2)}, opt_level)
        tx0 = sgd_by_group({"m0": 0.25}, momentum=0.125)
        tx1 = sgd_by_group({"m1": 0.5}, momentum=0.25)
        o0, o1 = tx0.init(p0), tx1.init(p1)
        grads_seen = []
        for i in range(iters):
            g0 = jax.grad(lambda p: model_loss(p["m0"]))(p0)
            g1 = jax.grad(lambda p: model_loss(p["m1"]))(p1)
            if i not in skip:
                grads_seen.append((g0, g1))
            if (i, 0) not in skip_pairs:
                u, o0 = tx0.update(g0, o0, p0)
                p0 = optax.apply_updates(p0, u)
            if (i, 1) not in skip_pairs:
                u, o1 = tx1.update(g1, o1, p1)
                p1 = optax.apply_updates(p1, u)
        return grads_seen, (p0, p1)

    for case in case_grid(opt_level):
        inject, wb = case["inject_inf"], case["which_backward"]
        iters = 3 if inject >= 0 else 2
        # overflow in backward j skips optimizer j only (scale_loss binds
        # one optimizer per context here, reference :446-449).
        skip_pairs = {(inject, wb)} if inject >= 0 else set()
        skip = {inject} if inject >= 0 else set()
        ref_grads, (ref_p0, ref_p1) = run_reference(iters, skip, skip_pairs)

        tx0 = sgd_by_group({"m0": 0.25}, momentum=0.125)
        tx1 = sgd_by_group({"m1": 0.5}, momentum=0.25)
        a0 = init_amp(opt_level, tx0, num_losses)
        a1 = init_amp(opt_level, tx1, num_losses)
        # Scalers are GLOBAL per loss_id in the reference (_amp_state
        # .loss_scalers), shared across optimizers: keep them in state0.
        s0 = seed_scales(a0.init({"m0": make_model(1)}), num_losses)
        s1 = a1.init({"m1": make_model(2)})

        unskipped = 0
        for i in range(iters):
            pc0, pc1 = a0.model_params(s0), a1.model_params(s1)
            g0 = jax.grad(lambda p: a0.scale_loss(
                a0.run(lambda q: model_loss(q["m0"]), p), s0,
                loss_id=loss_ids[0]))(pc0)
            g1 = jax.grad(lambda p: a1.scale_loss(
                a1.run(lambda q: model_loss(q["m1"]), p), s0,
                loss_id=loss_ids[1]))(pc1)
            if i == inject:
                if wb == 0:
                    g0 = inject_inf_into(g0, "m0", case["inject_inf_loc"])
                else:
                    g1 = inject_inf_into(g1, "m1", case["inject_inf_loc"])

            u0, f0 = a0.unscale_gradients(s0, g0, loss_id=loss_ids[0])
            u1, f1 = a0.unscale_gradients(s0, g1, loss_id=loss_ids[1])
            s0, ov0 = a0.update_scaler(s0, loss_ids[0], f0)
            s0, ov1 = a0.update_scaler(s0, loss_ids[1], f1)

            if i != inject:
                tree_allclose(u0, ref_grads[unskipped][0], rtol=1e-6, atol=0)
                tree_allclose(u1, ref_grads[unskipped][1], rtol=1e-6, atol=0)
                unskipped += 1

            s0 = a0.step_if(s0, u0, ov0)
            s1 = a1.step_if(s1, u1, ov1)

        tree_allclose(s0.master_params, ref_p0, rtol=1e-6, atol=0)
        tree_allclose(s1.master_params, ref_p1, rtol=1e-6, atol=0)


# ---------------------------------------------------------------------------
# topology 4: 3 models, 2 losses, 2 optimizers; loss1 spans both optimizers
# (reference :516-762)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("use_multiple_loss_scalers", (True, False))
@pytest.mark.parametrize("opt_level", OPT_LEVELS)
def test_3models2losses2optimizers(opt_level, use_multiple_loss_scalers):
    num_losses = 2 if use_multiple_loss_scalers else 1
    loss_ids = [0, 1] if use_multiple_loss_scalers else [0, 0]

    # optimizer0: model0 (lr .25) + model1 (lr 1.0), momentum .5;
    # optimizer1: model2 (lr .5), momentum .25 (reference :585-590).
    # loss0 = m0 + m1 (optimizer0 only); loss1 = m2 + m1 (both optimizers).
    def make_txs():
        return (sgd_by_group({"m0": 0.25, "m1": 1.0}, momentum=0.5),
                sgd_by_group({"m2": 0.5}, momentum=0.25))

    def loss0(p0):
        return model_loss(p0["m0"]) + model_loss(p0["m1"])

    def loss1(p0, p1):
        return model_loss(p1["m2"]) + model_loss(p0["m1"])

    def run_reference(iters, skip_pairs):
        p0 = reference_dtype_params(
            {"m0": make_model(1), "m1": make_model(2)}, opt_level)
        p1 = reference_dtype_params({"m2": make_model(3)}, opt_level)
        tx0, tx1 = make_txs()
        o0, o1 = tx0.init(p0), tx1.init(p1)
        grads_seen = []
        skipped_iters = {i for i, _ in skip_pairs}
        for i in range(iters):
            g0 = jax.grad(loss0)(p0)
            g1p0, g1p1 = jax.grad(loss1, argnums=(0, 1))(p0, p1)
            if i not in skipped_iters:
                grads_seen.append((tree_add(g0, g1p0), g1p1))
            if (i, 0) not in skip_pairs:
                u, o0 = tx0.update(tree_add(g0, g1p0), o0, p0)
                p0 = optax.apply_updates(p0, u)
            if (i, 1) not in skip_pairs:
                u, o1 = tx1.update(g1p1, o1, p1)
                p1 = optax.apply_updates(p1, u)
        return grads_seen, (p0, p1)

    for case in case_grid(opt_level,
                          which_models_by_backward={0: (0, 1), 1: (2, 1)}):
        inject, wb, wm = (case["inject_inf"], case["which_backward"],
                          case["which_model"])
        iters = 3 if inject >= 0 else 2
        # Overflow in backward 0 skips optimizer0; overflow in backward 1
        # skips BOTH (scale_loss(loss1, [optimizer0, optimizer1]),
        # reference :605-617 variant runs).
        if inject >= 0:
            skip_pairs = ({(inject, 0)} if wb == 0
                          else {(inject, 0), (inject, 1)})
        else:
            skip_pairs = set()
        ref_grads, (ref_p0, ref_p1) = run_reference(iters, skip_pairs)

        tx0, tx1 = make_txs()
        a0 = init_amp(opt_level, tx0, num_losses)
        a1 = init_amp(opt_level, tx1, num_losses)
        s0 = seed_scales(a0.init({"m0": make_model(1), "m1": make_model(2)}),
                         num_losses)
        s1 = a1.init({"m2": make_model(3)})

        unskipped = 0
        for i in range(iters):
            pc0, pc1 = a0.model_params(s0), a1.model_params(s1)
            g0 = jax.grad(lambda p: a0.scale_loss(
                a0.run(loss0, p), s0, loss_id=loss_ids[0]))(pc0)
            g1p0, g1p1 = jax.grad(
                lambda p, q: a0.scale_loss(
                    a0.run(lambda pp, qq: loss1(pp, qq), p, q), s0,
                    loss_id=loss_ids[1]),
                argnums=(0, 1))(pc0, pc1)
            if i == inject:
                if wb == 0:
                    g0 = inject_inf_into(g0, f"m{wm}",
                                         case["inject_inf_loc"])
                elif wm == 2:
                    g1p1 = inject_inf_into(g1p1, "m2",
                                           case["inject_inf_loc"])
                else:
                    g1p0 = inject_inf_into(g1p0, "m1",
                                           case["inject_inf_loc"])

            u0, f0 = a0.unscale_gradients(s0, g0, loss_id=loss_ids[0])
            u1p0, f1a = a0.unscale_gradients(s0, g1p0, loss_id=loss_ids[1])
            u1p1, f1b = a0.unscale_gradients(s0, g1p1, loss_id=loss_ids[1])
            f1 = jnp.logical_and(f1a, f1b)  # one overflow buf per backward
            s0, ov0 = a0.update_scaler(s0, loss_ids[0], f0)
            s0, ov1 = a0.update_scaler(s0, loss_ids[1], f1)

            if i != inject:
                tree_allclose(tree_add(u0, u1p0), ref_grads[unskipped][0],
                              rtol=1e-6, atol=0)
                tree_allclose(u1p1, ref_grads[unskipped][1],
                              rtol=1e-6, atol=0)
                unskipped += 1

            s0 = a0.step_if(s0, tree_add(u0, u1p0),
                            jnp.logical_or(ov0, ov1))
            s1 = a1.step_if(s1, u1p1, ov1)

        tree_allclose(s0.master_params, ref_p0, rtol=1e-6, atol=0)
        tree_allclose(s1.master_params, ref_p1, rtol=1e-6, atol=0)
