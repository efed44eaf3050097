"""Tracing / profiling annotations (SURVEY.md §5.1).

The reference sprinkled NVTX ranges at hot spots
(``apex/parallel/sync_batchnorm.py:66,84,129``,
``sync_batchnorm_kernel.py:11-47``) and drove nsight via
``torch.cuda.cudart().cudaProfilerStart/Stop``
(``tests/distributed/DDP/ddp_race_condition_test.py:44,66``) plus a
``--prof`` early-exit loop in the imagenet example
(``examples/imagenet/main_amp.py:63-64,311-334``).

TPU equivalents:

- :func:`nvtx_range` — ``jax.named_scope`` (names the HLO ops, visible in
  XProf's trace viewer and HLO graphs) combined with
  ``jax.profiler.TraceAnnotation`` (names the host-side section);
- :func:`range_push` / :func:`range_pop` — the imperative NVTX API shape;
- :func:`profiler_start` / :func:`profiler_stop` — capture an XProf trace
  to a log directory (view with TensorBoard's profile plugin or
  xprof);
- :func:`annotate` — decorator form for step functions.
"""

from __future__ import annotations

import contextlib
from typing import Callable, List, Optional

import jax

#: The ``jax.named_scope``s a compiled train step carries, in step order:
#: the O2 cast of the master weights to the compute copy, the gradient
#: exchange (``ddp_allreduce`` sits inside it), the unscale and finite
#: check, the loss-scale transition, and the optimizer update with its
#: overflow skip.  Forward and backward are autodiff's own ``jvp(...)``
#: and ``transpose(jvp(...))``.  Defined here and nowhere else in the
#: program; whatever reads a compiled step's ``op_name``s
#: (:class:`apex_tpu.obs.stepclass.TrainStepClassifier`, the benchmark's
#: ``scopes.py``) matches these strings.  A scope is metadata on the
#: instructions it covers and adds no operation.
(AMP_CAST, AMP_REDUCE, AMP_UNSCALE, AMP_SCALER_UPDATE,
 AMP_OPTIMIZER_STEP) = TRAIN_STEP_SCOPES = (
    "amp_cast", "amp_reduce", "amp_unscale", "amp_scaler_update",
    "amp_optimizer_step")

#: The scopes the models open besides their flax module names: the
#: feed-forward block (``ffn_in`` -> activation -> ``ffn_out``) and the
#: two loss functions, each under its own name.
MLP, LM_LOSS, PRETRAINING_LOSS = MODEL_SCOPES = (
    "mlp", "lm_loss", "pretraining_loss")

#: The scopes of the DeepSeek-V3-shaped decoder
#: (:mod:`apex_tpu.models.deepseek_v3`) and of the expert layer it runs
#: (:mod:`apex_tpu.parallel.moe`, which opens the first three): the
#: router (its product, the scores, the choice and the weights); the
#: sort of (token, expert) pairs, the gathers into expert order and back
#: and the weighted sum; the grouped products of the experts held; the
#: shared experts; and latent attention's projections with the latent
#: norm, the rotary and the assembly of q and k.  ``moe_experts`` and
#: ``moe_shared`` lie inside ``mlp``; ``mla_project`` inside the
#: ``attention`` module.
(MOE_ROUTE, MOE_DISPATCH, MOE_EXPERTS, MOE_SHARED,
 MLA_PROJECT) = MOE_SCOPES = (
    "moe_route", "moe_dispatch", "moe_experts", "moe_shared", "mla_project")

#: The scopes of a Kimi Delta Attention layer
#: (:mod:`apex_tpu.models.kimi_linear`), all inside its ``attention``
#: module: the five input projections, the two low-rank gates, the gated
#: head norm and the output projection; the short convolutions with
#: their SiLU and the L2 norm of q and k; and the chunkwise gated delta
#: rule (:mod:`apex_tpu.attention.gated_delta`, which opens it itself,
#: in its loop bodies, around its walk's kernels and in its hand-written
#: backward too).
KDA_PROJECT, KDA_CONV, KDA_RECURRENCE = KDA_SCOPES = (
    "kda_project", "kda_conv", "kda_recurrence")

#: The scopes of a gated short-convolution mixer
#: (:mod:`apex_tpu.models.lfm2_moe`), inside its ``conv`` module: the
#: in- and out-projections; and the gate, ``C * conv(B * u)`` (the two
#: products and the causal taps), open in the forward pass and in what
#: the backward pass recomputes of it.
SHORTCONV_PROJECT, SHORTCONV_GATE = SHORTCONV_SCOPES = (
    "shortconv_project", "shortconv_gate")

#: The scope of a sliding-window attention layer's windowed flash call
#: (:mod:`apex_tpu.models.laguna`), inside its ``attention`` module:
#: the window kernels and what their call reshapes, forward, backward and
#: what remat recomputes.
SWA_WINDOW, = SWA_SCOPES = ("swa_window",)


@contextlib.contextmanager
def nvtx_range(name: str):
    """Named region covering both the traced computation (HLO metadata)
    and host time (profiler TraceAnnotation)."""
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield


_range_stack: List[contextlib.ExitStack] = []


def range_push(name: str) -> None:
    """Imperative begin (``torch.cuda.nvtx.range_push`` shape)."""
    es = contextlib.ExitStack()
    es.enter_context(nvtx_range(name))
    _range_stack.append(es)


def range_pop() -> None:
    """Imperative end (``torch.cuda.nvtx.range_pop``)."""
    if _range_stack:
        _range_stack.pop().close()


def annotate(name: Optional[str] = None) -> Callable:
    """Decorator: run the function inside a named range."""
    def deco(fn):
        label = name or fn.__name__

        def wrapped(*args, **kwargs):
            with nvtx_range(label):
                return fn(*args, **kwargs)

        wrapped.__name__ = fn.__name__
        wrapped.__doc__ = fn.__doc__
        return wrapped
    return deco


_trace_active = False


def profiler_start(logdir: str = "/tmp/apex_tpu_trace") -> None:
    """Begin an XProf capture (``cudaProfilerStart`` analog)."""
    global _trace_active
    if not _trace_active:
        jax.profiler.start_trace(logdir)
        _trace_active = True


def profiler_stop() -> None:
    """End the capture and flush the trace (``cudaProfilerStop``)."""
    global _trace_active
    if _trace_active:
        jax.profiler.stop_trace()
        _trace_active = False
