"""What stands between the repo and a number from the wrong device: the
compile-cache placement, the peak table, the smoke's and the spawner's
refusals, and the reader of Mosaic kernel names."""

import json
import os
import re
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import pytest

from apex_tpu.ops import mosaic_kernels
from apex_tpu.parallel import multiproc
from apex_tpu.utils import chip_peaks, compile_cache

REPO = Path(__file__).resolve().parents[2]
#: spelled in two halves so that a grep for the option finds its one setter
CACHE_OPTION = "jax_compilation_" + "cache_dir"
METADATA_OPTION = "jax_compilation_cache_include_metadata_in_key"
TRACEBACK_OPTION = "jax_traceback_in_locations_limit"


@pytest.fixture
def cache_updates(monkeypatch):
    """``enable()`` as a chip process sees it, with the config writes
    captured instead of applied to this test process."""
    updates = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    return updates


def test_compile_cache_env_variable_wins_and_code_sets_nothing(
        monkeypatch, cache_updates):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.enable() == "/somewhere/else"
    assert [u for u in cache_updates if u[0] == CACHE_OPTION] == []


def test_compile_cache_default_is_a_fixed_path_in_the_checkout(
        monkeypatch, cache_updates):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable()
    assert first == str(REPO / ".jax_cache") == compile_cache.enable()
    assert [u for u in cache_updates if u[0] == CACHE_OPTION] == \
        [(CACHE_OPTION, first)] * 2
    assert not first.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in first


@pytest.mark.parametrize("from_env", [None, "/somewhere/else"])
def test_compile_cache_keeps_metadata_in_its_key(monkeypatch, cache_updates,
                                                 from_env):
    """Wherever the cache lives: per-layer metrics read ``op_name`` from
    the compiled program, and JAX's key strips it unless told not to."""
    if from_env:
        monkeypatch.setenv(compile_cache.ENV_VAR, from_env)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    compile_cache.enable()
    assert (METADATA_OPTION, True) in cache_updates
    assert (TRACEBACK_OPTION, 1) in cache_updates
    for option in (METADATA_OPTION, TRACEBACK_OPTION):   # they exist
        assert hasattr(jax.config, option)


def test_compile_cache_sets_nothing_on_the_cpu_platform(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    options = (CACHE_OPTION, METADATA_OPTION, TRACEBACK_OPTION)
    before = [getattr(jax.config, o) for o in options]
    assert compile_cache.enable() is None
    assert [getattr(jax.config, o) for o in options] == before


def test_no_other_code_sets_a_compile_cache_directory():
    setters = [
        str(p.relative_to(REPO))
        for pattern in ("*.py", "apex_tpu/**/*.py", "tools/*.py",
                        "examples/*.py", "tests/conftest.py")
        for p in REPO.glob(pattern)
        if CACHE_OPTION in p.read_text()]
    assert setters == ["apex_tpu/utils/compile_cache.py"]


def test_the_benchmark_is_the_one_under_benchmark():
    """``python3 -m benchmark.run`` is the measurement and
    ``chip_smoke.py`` the bring-up check: no other entry point stands in
    the root, and nothing imports the harnesses that once stood beside
    them."""
    assert sorted(p.name for p in REPO.glob("*.py")) == [
        "__graft_entry__.py", "chip_smoke.py", "setup.py"]
    gone = re.compile(
        r"^\s*(?:import|from)\s+(?:tools\.)?(?:bench|kernel_bench|"
        r"bench_variance|perf_timeline|fusion_roofline)\b", re.M)
    importers = [
        str(p.relative_to(REPO))
        for top in ("apex_tpu", "tools", "tests", "examples")
        for p in (REPO / top).rglob("*.py")
        if gone.search(p.read_text())]
    assert importers == []


def test_peak_table_resolves_the_kind_the_chip_reports():
    peak = chip_peaks.chip_peak("TPU v5 lite")
    assert peak.bf16_flops_per_s == 197e12
    assert peak.hbm_bytes_per_s == 819e9


def test_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks.chip_peak("TPU v9 imaginary")
    with pytest.raises(ValueError, match="'cpu'"):
        chip_peaks.chip_peak()        # this test process runs on the CPU


def test_chip_smoke_without_a_tpu_fails_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(REPO),
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout == ""


def test_chip_smoke_last_line_is_the_verdict_and_nothing_else():
    """The chip check reads the last line of standard output and wants
    exactly ``ok`` and ``device`` {platform, kind, count} there (the
    first submission of PR 21 was refused for carrying the whole report
    on that line).  The explicit dry run takes the same exit path."""
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--cpu-dry-run"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(REPO),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    *_, report, verdict = out.stdout.splitlines()
    assert json.loads(verdict) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    phases = json.loads(report)["report"]["phases"]
    assert sorted(phases) == ["kernels", "serve", "train"]
    assert phases["train"]["compile_s"] is None      # no CPU time reported


def test_spawn_refuses_several_processes_on_a_tpu_host(monkeypatch):
    """On a host with TPU chips every child would claim all of them; the
    second fails at libtpu's lockfile (PR 21, four-chip host).  spawn()
    says so at once instead."""
    monkeypatch.setattr(multiproc, "_local_tpu_device_nodes",
                        lambda: ["/dev/vfio/0", "/dev/vfio/1"])
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(multiproc.ClusterInitError,
                       match="ONE process per host"):
        multiproc.spawn(["-c", "print(1)"], world_size=2)
    # children kept off the chips (the CPU drills) are not its business,
    # nor is a single process
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert not multiproc._children_would_share_the_chips(2)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert not multiproc._children_would_share_the_chips(1)


def test_mosaic_kernels_reads_names_off_compiled_hlo():
    # lines as the v5e compiler printed them (PR 21), bodies elided
    hlo = '''
  %layer_norm_bwd.1 = (f32[16384,4096]{1,0:T(8,128)}, f32[1,4096]{1,0:T(1,128)}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(<lambda>)/transpose(jvp(jit(_backward)))/layer_norm_bwd/pallas_call" stack_frame_id=7}
  %c.2 = bf16[8,2048,768]{2,1,0} custom-call(%b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(GPTModel)/block_0/attention/jit(_flash_fwd)/flash_fwd/pallas_call"}
  %c.3 = f32[2]{0} custom-call(%b), custom_call_target="Sharding", metadata={op_name="jit(step)/sharding_constraint"}
  %c.4 = f32[2]{0} custom-call(%b), custom_call_target="tpu_custom_call"
'''
    assert mosaic_kernels(hlo) == ["<no op_name>", "flash_fwd",
                                   "layer_norm_bwd"]
    assert mosaic_kernels("ENTRY %main { ROOT %x = f32[] add(%a, %b) }") \
        == []
