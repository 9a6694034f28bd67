"""A configuration brings its own architecture: the program is checked
against the fields its reference declares, and a routed-expert
configuration (``bench/tests/data/small_moe.json``, reference ``moe_gqa``)
has a reference of its own (``test_bench_run`` and ``test_bench_control``
serve it through the whole harness and judge it as the dense cells)."""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, harness
from bench.tests import small

# today's fields and values for the two dense configurations, one by one
DENSE_FIELDS = {
    "qwen3-1.7b": {"d_model": 2048, "d_ff": 6144, "num_layers": 28, "num_heads": 16,
                   "num_kv_heads": 8, "head_dim_": 128, "vocab_size": 151936,
                   "rope_theta": 1000000.0, "norm_eps": 1e-06, "norm": "rmsnorm",
                   "qk_norm": True, "mlp": "swiglu", "use_bias": False,
                   "tie_embeddings": True, "family": "dense"},
    "starcoder2-3b": {"d_model": 3072, "d_ff": 12288, "num_layers": 30, "num_heads": 24,
                      "num_kv_heads": 2, "head_dim_": 128, "vocab_size": 49152,
                      "rope_theta": 999999.4420358813, "norm_eps": 1e-05,
                      "norm": "layernorm", "qk_norm": False, "mlp": "gelu",
                      "use_bias": True, "tie_embeddings": True, "family": "dense"},
}


def moe_config() -> dict:
    return common.load_json(small.SMALL_MOE)


@pytest.mark.parametrize("name", sorted(DENSE_FIELDS))
def test_dense_program_fields_are_todays(name):
    config = common.load_json(common.BENCH / "configs" / f"{name}.json")
    fields = common.reference_module(config).program_fields(config)
    want = DENSE_FIELDS[name]
    assert list(fields) == list(want)
    for k, v in want.items():
        assert fields[k] == v and type(fields[k]) is type(v), k
    harness.program_config(config)


def test_program_config_compares_exactly_the_reference_fields(monkeypatch):
    """Every mismatch is listed, and an attribute the program lacks is one."""
    config = moe_config()
    ref = types.SimpleNamespace(program_fields=lambda c: {
        "family": "dense", "num_experts": 8, "d_model": 64, "window_layers": 3})
    monkeypatch.setattr(common, "reference_module", lambda c: ref)
    with pytest.raises(ValueError) as e:
        harness.program_config(config)
    msg = str(e.value)
    for k in ("family", "d_model", "window_layers"):
        assert repr(k) in msg
    assert "num_experts" not in msg


def test_reference_without_program_fields_is_refused(monkeypatch):
    monkeypatch.setattr(common, "reference_module", lambda c: types.SimpleNamespace())
    with pytest.raises(ValueError, match="program_fields"):
        harness.program_config(moe_config())


def test_moe_config_with_the_dense_reference_is_refused():
    config = {**moe_config(), "reference": "dense_gqa"}
    with pytest.raises(ValueError, match="family"):
        harness.build(config, 1)


def test_moe_reference_covers_only_all_sparse_layers():
    ref = common.reference_module(moe_config())
    for key, value in (("decoder_sparse_step", 2), ("mlp_only_layers", [0])):
        with pytest.raises(ValueError, match="routed experts"):
            ref.dims({**moe_config(), key: value})


@pytest.mark.parametrize("norm_topk", [True, False])
def test_moe_gates_take_the_top_k(norm_topk):
    config = moe_config()
    ref = common.reference_module(config)
    m = {**ref.dims(config), "norm_topk": norm_topk}
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((m["d"], m["E"])), jnp.float32)
    h = jnp.asarray(rng.standard_normal((5, m["d"])), jnp.float32)
    g = np.asarray(ref.gates(m, {"w": w}, h, False))
    logits = np.asarray(h) @ np.asarray(w)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, -1)[:, :m["k"]]
    want = np.zeros_like(probs)
    np.put_along_axis(want, top, np.take_along_axis(probs, top, -1), -1)
    if norm_topk:
        want /= want.sum(-1, keepdims=True)
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)
