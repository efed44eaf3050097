"""Attribute profiled device time to conv layers for the RN50 campaign.

Joins two artifacts of one bench step:
- the compiled HLO: every convolution sits in its own fused computation;
  the fusion instruction name is what the profiler reports, and the
  conv's ``metadata op_name`` carries the flax module path (layer +
  fwd/bwd role), and
- an xplane profile of a few steps (op name -> device time),

and prints per-conv time + achieved MFU *in situ* — no microbenchmark
artifacts (dispatch overhead, CSE, false dependencies); the numbers are
the real step's.  This is how a step profile that is mostly
convolution fusions decomposes into actionable layers.

FLOPs per conv: 2 * prod(output dims) * prod(window sizes) * C_contract,
where C_contract is the lhs dim labeled ``f`` in dim_labels — correct
for forward, input-grad and filter-grad spellings alike.

Usage: python tools/conv_attrib.py [resnet50|resnet50_s2d] [O2] [batch]
"""

import collections
import json
import re
import shutil
import sys
import time
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

_SHAPE_RE = re.compile(r"(bf16|f16|f32|s8|u8|s32)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\S+)")
_CONV_RE = re.compile(
    r"convolution\(%?([\w.\-]+), %?([\w.\-]+)\).*?"
    r"window={size=([0-9x]+)[^}]*}.*?dim_labels=(\S+?),.*?"
    r"op_name=\"([^\"]+)\"")
_CALLS_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? fusion\(.*calls=%?([\w.\-]+)")


def _dims(shape_str):
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


def parse_hlo(hlo: str):
    """-> {fusion instr name: conv record} for every convolution."""
    comp_shapes = collections.defaultdict(dict)   # comp -> name -> dims
    comp_convs = {}                               # comp -> record
    cur = None
    for raw in hlo.splitlines():
        line = raw.strip()
        if line.endswith("{") and " = " not in line and "(" in line:
            cur = line.split()[0].lstrip("%").split("(")[0]
            continue
        im = _INSTR_RE.match(raw)
        if im:
            comp_shapes[cur][im.group(1)] = _dims(im.group(2))
        cm = _CONV_RE.search(line)
        if cm and im:
            lhs, rhs, window, dim_labels, op_name = cm.groups()
            out = _dims(im.group(2))
            lhs_dims = comp_shapes[cur].get(lhs)
            if out is None or lhs_dims is None:
                continue
            # Per-output contraction = rhs "i" dim (robust to grouped/
            # depthwise convs, where the lhs "f" dim overcounts by the
            # group count)
            rhs_dims = comp_shapes[cur].get(rhs)
            rhs_label = dim_labels.split("_")[1].split("->")[0]
            if rhs_dims is not None and "i" in rhs_label:
                cin = rhs_dims[rhs_label.index("i")]
            else:
                lhs_label = dim_labels.split("_")[0]
                cin = lhs_dims[lhs_label.index("f")]
            win = 1
            for w in window.split("x"):
                win *= int(w)
            flops = 2.0 * cin * win
            for d in out:
                flops *= d
            layer = re.sub(r"^jit\(\w+\)/", "", op_name)
            comp_convs[cur] = {
                "layer": layer, "flops": flops,
                # the true forward is the jvp spelling; dgrad is ALSO
                # b01f (rhs_reversal + base dilation), so dim_labels
                # can't distinguish them — the op_name can
                "fwd": not layer.startswith("transpose"),
                "out": out, "window": window, "cin": cin}
    # The naive flops formula is only trustworthy for the forward
    # spelling (b01f lhs); gradient convs use full-correlation spellings
    # whose padded window taps would massively overcount.  dgrad and
    # wgrad each cost the same MACs as their forward conv, so assign
    # every transpose conv its layer's forward figure.
    fwd_flops = {}
    for rec in comp_convs.values():
        if rec["fwd"]:
            layer = rec["layer"].split(")/")[-1]
            fwd_flops[layer] = rec["flops"]
    for rec in comp_convs.values():
        if not rec["fwd"]:
            layer = rec["layer"].split(")/")[-1]
            rec["flops"] = fwd_flops.get(layer, rec["flops"])
    # fusion instruction -> computation
    result = {}
    for raw in hlo.splitlines():
        fm = _CALLS_RE.match(raw)
        if fm and fm.group(2) in comp_convs:
            result[fm.group(1)] = comp_convs[fm.group(2)]
    return result


def main():
    model = sys.argv[1] if len(sys.argv) > 1 else "resnet50"
    opt_level = sys.argv[2] if len(sys.argv) > 2 else "O2"
    batch = int(sys.argv[3]) if len(sys.argv) > 3 else 256
    from apex_tpu import amp
    from apex_tpu.models.resnet import ARCHS
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.utils.chip_peaks import chip_peak
    import jax.numpy as jnp

    peak = chip_peak().bf16_flops_per_s
    m = ARCHS[model]()
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, 224, 224, 3),
                          jnp.float32)
    y = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, 1000)
    variables = m.init(jax.random.PRNGKey(2), x[:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    a = amp.initialize(optimizer=FusedAdam(lr=1e-3), opt_level=opt_level,
                       verbosity=0)
    state = a.init(params)

    def loss_fn(p, xb, yb):
        logits, _ = m.apply({"params": p, "batch_stats": batch_stats},
                            xb, train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))

    step = jax.jit(amp.make_train_step(a, loss_fn), donate_argnums=(0,))
    compiled = step.lower(state, x, y).compile()
    convs = parse_hlo(compiled.as_text())

    iters = 6
    st, _ = compiled(state, x, y)
    jax.block_until_ready(st)
    logdir = "/tmp/apex_tpu_conv_attrib"
    shutil.rmtree(logdir, ignore_errors=True)
    with jax.profiler.trace(logdir):
        for _ in range(iters):
            st, mtr = compiled(st, x, y)
        jax.block_until_ready(st)
    time.sleep(1)

    from apex_tpu.obs.xplane import parse_xplane
    by_name, _, total = parse_xplane(logdir)

    rows = []
    conv_time = 0.0
    matched = set()
    for name, dur_ps in by_name.items():
        rec = convs.get(name)
        if rec is None:
            continue
        matched.add(name)
        dur_s = dur_ps / 1e12 / iters
        conv_time += dur_s
        rows.append({"op": name, "layer": rec["layer"],
                     "ms": round(dur_s * 1e3, 3),
                     "mfu": round(rec["flops"] / dur_s / peak, 3),
                     "gflops": round(rec["flops"] / 1e9, 1),
                     "out": rec["out"], "win": rec["window"],
                     "cin": rec["cin"]})
    rows.sort(key=lambda r: -r["ms"])
    for r in rows:
        print(json.dumps(r))
    step_s = total / 1e12 / iters
    print(json.dumps({
        "conv_ms_per_step": round(conv_time * 1e3, 2),
        "device_ms_per_step": round(step_s * 1e3, 2),
        "conv_frac": round(conv_time / step_s, 3),
        "hlo_convs": len(convs), "profiled_convs": len(rows),
        "conv_mfu": round(sum(c["flops"] for c in convs.values())
                          / (conv_time + 1e-12) / peak, 4)
        if len(rows) == len(convs) else None}))


if __name__ == "__main__":
    main()
