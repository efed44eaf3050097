"""Slow-tier convergence targets (VERDICT r2 item 5).

Miniature versions of the three ``tools/convergence_run.py`` proofs run
continuously on CPU so the training loops, data plumbing, and targets
logic cannot rot; the full-scale on-chip records live in
``CONVERGENCE_r03.json`` (regenerated by the tool on hardware, where
the real targets are asserted via ``APEX_TPU_TEST_PLATFORM``).
"""

import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "tools"))

import convergence_run as cr  # noqa: E402

# hardware runs set APEX_TPU_TEST_PLATFORM=tpu — gate on the resolved
# backend, not the string
ON_HW = jax.default_backend() != "cpu"
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def corpus():
    return cr._corpus()


def test_gpt_pysrc_learns(corpus):
    """60 miniature steps must beat a near-chance bar (uniform over the
    ~100 byte values in use is ~4.6 nats); the on-chip run holds the
    real 1.75-nat target."""
    rec = cr.run_gpt_pysrc(steps=60, batch=4, seq=128, hidden=64,
                           layers=2, heads=2, corpus=corpus,
                           target_val_nats=4.2)
    assert rec["ok"], rec
    assert rec["val_nats_per_byte"] < rec["train_nats"] + 1.0  # no blowup


def test_bert_mlm_learns(corpus):
    rec = cr.run_bert_mlm(steps=60, batch=4, seq=64, hidden=64,
                          layers=2, heads=2, corpus=corpus,
                          target_mlm_nats=4.6)
    assert rec["ok"], rec
    assert rec["val_mlm_nats"] < rec["chance_nats"]


def test_corpus_anchors_ordering(corpus):
    """The external floors (VERDICT r3 weak #4) must be internally
    consistent: more context → lower held-out n-gram cross-entropy, and
    the dictionary compressors (long-range matches) must beat every
    n-gram.  Pins the counting/indexing of corpus_anchors."""
    a = cr.corpus_anchors(corpus)
    n1, n2, n3 = (a[f"ngram{k}_nats_per_byte"] for k in (1, 2, 3))
    assert n1 > n2 > n3 > 0
    assert n1 < 5.55  # under chance ln(256)
    for comp in ("gzip", "bz2", "lzma"):
        assert 0 < a[f"{comp}_nats_per_byte"] < n3
    assert a["train_bytes"] > a["val_bytes"] > 0


def test_dcgan_fp16_overflow_recovers():
    """fp16 + 2^16 initial scale must overflow at least once and keep
    training with finite losses after the last overflow — the
    two-scaler failure-detection machinery under real GAN dynamics
    (organic overflow; fp16 behaves on the CPU backend — the first
    overflow lands ~step 33 with the current seeds)."""
    rec = cr.run_dcgan_two_scaler(steps=60, batch=8)
    assert rec["d_overflows"] + rec["g_overflows"] > 0, rec
    assert rec["ok"], rec


def test_dcgan_injection_mode_proves_scaler_independence():
    """bf16 + targeted faults (the chip-record mode): an inf in the
    REAL batch must trip only D's scaler — G's loss never sees it."""
    rec = cr.run_dcgan_two_scaler(steps=12, batch=8,
                                  half_dtype="bfloat16", inject=(3, 7))
    assert rec["scaler_independence_ok"], rec
    assert rec["d_overflows"] >= 2 and rec["g_overflows"] >= 1, rec
    assert rec["ok"], rec


@pytest.mark.skipif(not ON_HW, reason="full-scale targets run on chip")
def test_onchip_record_meets_targets():
    import json
    root = Path(__file__).resolve().parents[2]
    path = root / "CONVERGENCE_r04.json"
    if not path.exists():
        path = root / "CONVERGENCE_r03.json"
    rec = json.loads(path.read_text())
    assert rec["all_ok"], rec
    assert rec["gpt_pysrc"]["val_nats_per_byte"] <= \
        rec["gpt_pysrc"]["target_val_nats"]
    assert rec["bert_mlm"]["val_mlm_nats"] <= \
        rec["bert_mlm"]["target_mlm_nats"]
    assert rec["dcgan_two_scaler"]["d_overflows"] \
        + rec["dcgan_two_scaler"]["g_overflows"] > 0
    if "anchors" in rec:  # r04+: externally-anchored floors
        assert rec["gpt_pysrc"]["beats_ngram3"]
        assert rec["bert_mlm"]["beats_ngram1"]
    if "gpt_pysrc_large" in rec:  # r04+: the compressor-beating bar
        assert rec["gpt_pysrc_large"]["beats_lzma"]


def test_o4_mnist_lane_miniature():
    """The fp8 O4-vs-O1 lane at reduced steps: curves finite, O4 within
    the band, fp8 telemetry recorded."""
    rec = cr.run_o4_mnist(steps=80)
    assert rec["ok"], rec
    assert rec["fp8_final_scales"]["input"] != 1.0
    assert len(rec["o1_curve"]) == len(rec["o4_curve"])


def test_int8_kv_decode_lane_miniature(corpus):
    """The int8-KV decode lane at reduced scale: the briefly-trained
    byte-LM's greedy stream must match the dense cache at the
    documented tolerance and be bitwise deterministic."""
    rec = cr.run_int8_kv_decode(train_steps=30, prompts=2,
                                prompt_len=32, decode_tokens=24,
                                corpus=corpus)
    assert rec["ok"], rec
    assert rec["bitwise_deterministic"]
    assert rec["token_match_rate"] >= rec["min_match_rate"]
