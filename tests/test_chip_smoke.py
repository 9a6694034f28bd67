"""``chip_smoke.py`` on the CPU: its phases at reduced width, its four-chip
placement checks on four forced host devices, and its refusal of any
backend other than TPU.  Also the pieces it leans on: the one interpret
switch and the compile-cache placement."""

import dataclasses
import importlib
import importlib.util
import inspect
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_reduced_config

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cs():
    return _load_chip_smoke()


SMALL = dict(n_slots=2, page_size=8, max_len=32, prefill_chunk=8)


def test_serve_and_reference_phases_reduced(cs):
    cfg = get_reduced_config("qwen3-1.7b")
    model, params = cs.build(cfg, 0)
    workload = cs.make_workload(cfg.vocab_size, 0, 3, 5, 20, 3)
    report, failures = cs.serve_phase(model, params, workload,
                                      cs.engine_config("mxfp4", **SMALL),
                                      cs.CompileTimer())
    assert failures == []
    assert report["requests"] == 3 and report["tokens"] == 9
    assert report["logit_rows_checked"] == 9
    assert report["jit_compiled_after"] == report["jit_compiled_before"]
    assert report["jit_compiled_before"]["decode_all"] == 1
    assert report["jit_compiled_before"]["prefill_all"] == 1

    for method, bound in cs.REL_L2_BOUND.items():
        ref, failures = cs.reference_phase(model, params, [w[1] for w in workload[:2]],
                                           cs.engine_config("dense", method=method, **SMALL))
        assert failures == []
        assert ref["method"] == method and ref["bound"] == bound
        assert max(ref["rel_l2"]) < 1e-4  # same numerics on the CPU
        assert ref["control_rel_l2"] > bound  # the bound discriminates


def test_serve_phase_flags_short_requests(cs):
    """A request that retires early (EOS) fails the exact-token check."""
    cfg = get_reduced_config("qwen3-1.7b")
    model, params = cs.build(cfg, 0)
    workload = cs.make_workload(cfg.vocab_size, 1, 2, 5, 12, 4)
    ecfg = cs.engine_config("mxfp4", **SMALL)
    # an EOS id equal to a token the engine emits ends that request early
    eng = cs.Engine(model, params, ecfg)
    for _, prompt, max_new in workload:
        eng.submit(prompt, max_new)
    first = eng.drain()[0].tokens[0]
    _, failures = cs.serve_phase(model, params, workload,
                                 dataclasses.replace(ecfg, eos_id=first),
                                 cs.CompileTimer())
    assert any("tokens, want" in f for f in failures), failures


_MULTICHIP = """
import dataclasses, json, sys
sys.path.insert(0, {root!r})
import chip_smoke as cs
from repro.configs import get_reduced_config
cfg = dataclasses.replace(get_reduced_config("qwen3-1.7b"), num_heads=8, num_kv_heads=4)
model, params = cs.build(cfg, 0)
workload = cs.make_workload(cfg.vocab_size, 0, 4, 5, 12, 3)
report, failures = cs.multichip_phase(
    model, params, workload,
    cs.engine_config("dense", n_slots=2, page_size=8, max_len=32, prefill_chunk=8), 4)
print(json.dumps({{"report": report, "failures": failures}}, default=str))
"""


def test_multichip_phase_on_four_host_devices():
    """TP=4 pool leaves span four devices, DP=4 replicas sit on four distinct
    devices, and both match the one-device baseline token for token — run in
    a child process, the only way to force four host devices."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    out = subprocess.run([sys.executable, "-c", _MULTICHIP.format(root=str(ROOT))],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    rep = res["report"]
    assert res["failures"] == []
    assert rep["tp_token_exact"] and rep["dp_token_exact"]
    assert set(rep["tp_pool_leaf_devices"].values()) == {4}
    homes = [tuple(d) for d in rep["dp_replica_devices"]]
    assert all(len(h) == 1 for h in homes) and len(set(homes)) == 4
    assert sum(rep["dp_requests_per_replica"]) == 4


def test_entry_refuses_non_tpu_backend(cs, capsys):
    assert jax.default_backend() != "tpu"
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no result line, nothing a reader could take as one
    assert "needs a TPU" in out.err


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


# ---------------------------------------------------------------------------
# the one interpret switch
# ---------------------------------------------------------------------------


def test_interpret_mode_follows_default_backend(monkeypatch):
    from repro.kernels import backend

    assert backend.interpret_mode() is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backend.interpret_mode() is False
    assert backend.interpret_mode(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert backend.interpret_mode() is True
    assert backend.interpret_mode(False) is False


def test_no_kernel_entry_point_defaults_to_interpret():
    entry_points = {
        "flash_attention": ("flash_attention", "mha_flash"),
        "hadamard_quant": ("hadamard_quest_quantize",),
        "kv_pack": ("kv_quant_pack", "kv_dequant_unpack"),
        "mxfp4_matmul": ("mxfp4_matmul",),
        "paged_attention": ("paged_attention",),
        "sr_hadamard_quant": ("sr_hadamard_quantize",),
    }
    for module, names in entry_points.items():
        mod = importlib.import_module(f"repro.kernels.{module}")
        for name in names:
            default = inspect.signature(getattr(mod, name)).parameters["interpret"].default
            assert default is None, (module, name)


# ---------------------------------------------------------------------------
# compile-cache placement
# ---------------------------------------------------------------------------


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_defaults_to_fixed_checkout_dir(tmp_path, monkeypatch,
                                                      restore_cache_dir):
    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache(tmp_path)
    assert path == str(tmp_path / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.CHECKOUT == ROOT
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_compile_cache_env_var_wins(tmp_path, monkeypatch, restore_cache_dir):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "elsewhere"))
    assert compile_cache.enable_compile_cache(tmp_path) == str(tmp_path / "elsewhere")
    assert jax.config.jax_compilation_cache_dir == before  # nothing else set
