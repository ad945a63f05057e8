"""Sharding-rule engine: divisibility fallback, axis uniqueness, profiles.

Pure-host logic tests (build a Mesh over 1 CPU device via AbstractMesh-style
shape reasoning is not needed — Mesh construction only needs device objects).
"""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed.sharding import default_rules, _fsdp_rules, vocab_pad_for


def fake_mesh(shape, axes):
    # sharding specs only consult mesh.shape — build a host-only mesh by
    # tiling the single CPU device (never used for execution).
    devs = np.tile(np.array(jax.devices()[:1]), int(np.prod(shape)))
    return jax.sharding.Mesh(devs.reshape(shape), axes)


MESH = fake_mesh((16, 16), ("data", "model"))
MESH3 = fake_mesh((2, 16, 16), ("pod", "data", "model"))


def test_divisible_dims_shard():
    cfg = configs.get_config("granite_3_2b")
    rules = default_rules(MESH, cfg)
    # d_ff 8192 % 16 == 0 → mlp shards on model
    assert rules.spec_for(("embed", "mlp"), (2048, 8192)) == P(None, "model")
    # batch over data
    assert rules.spec_for(("batch", None), (256, 4096)) == P("data", None)


def test_non_divisible_falls_back_to_replication():
    import dataclasses
    cfg = dataclasses.replace(configs.get_config("recurrentgemma_2b"),
                              ctx_parallel_attn=False)  # 10 heads, no CP
    rules = default_rules(MESH, cfg)
    spec = rules.spec_for(("batch", "heads", "seq_full", "head_dim"),
                          (256, 10, 4096, 256))
    assert spec == P("data", None, None, None)
    assert rules.rules["heads"] is None  # head rule disabled at build time


def test_non_divisible_heads_with_ctx_parallel_shard_seq():
    # the promoted production config: attention q-rows shard over model
    cfg = configs.get_config("recurrentgemma_2b")  # ctx_parallel_attn=True
    rules = default_rules(MESH, cfg)
    spec = rules.spec_for(("batch", "heads", "seq_full", "head_dim"),
                          (256, 10, 4096, 256))
    assert spec == P("data", None, "model", None)


def test_axis_used_at_most_once():
    cfg = configs.get_config("deepseek_moe_16b")   # kv_heads=16 divisible
    rules = default_rules(MESH, cfg)
    spec = rules.spec_for(("batch", "kv_heads", "kv_cache_seq", "head_dim"),
                          (128, 16, 32768, 128))
    # kv_heads takes 'model'; cache seq must NOT reuse it
    assert spec == P("data", "model", None, None)

    cfg2 = configs.get_config("granite_3_2b")      # kv_heads=8 not divisible
    rules2 = default_rules(MESH, cfg2)
    spec2 = rules2.spec_for(("batch", "kv_heads", "kv_cache_seq", "head_dim"),
                            (128, 8, 32768, 64))
    # kv_heads fell back → cache seq picks up 'model' (distributed decode)
    assert spec2 == P("data", None, "model", None)


def test_multipod_batch_spans_pod_and_data():
    cfg = configs.get_config("granite_3_2b")
    rules = default_rules(MESH3, cfg)
    assert rules.spec_for(("batch", None), (256, 4096)) == \
        P(("pod", "data"), None)


def test_fsdp_profile_shards_params_over_both_axes():
    import dataclasses
    cfg = dataclasses.replace(configs.get_config("deepseek_67b"),
                              sharding_profile="fsdp")
    rules = default_rules(MESH, cfg)
    # params: embed dim over (data, model) = 256-way ZeRO-3
    assert rules.spec_for(("embed", "mlp"), (8192, 22016)) == \
        P(("data", "model"), None)
    # batch over the same 256-way product
    assert rules.spec_for(("batch", None), (256, 4096)) == \
        P(("data", "model"), None)
    # no TP anywhere
    assert rules.rules["heads"] is None and rules.rules["mlp"] is None


def test_vocab_padding_divisibility():
    cfg = configs.get_config("granite_3_2b")  # vocab 49155 (odd)
    rules = default_rules(MESH, cfg)
    assert rules.spec_for(("vocab", "embed"), (49155, 2048)) == P(None, None)
    assert rules.spec_for(("vocab", "embed"), (49168, 2048)) == P("model", None)


def test_fsdp_profile_needs_explicit_opt_in():
    """The seed bug: sharding_profile="fsdp" alone (a scale annotation) must
    NOT strip TP — only fsdp=True opts a config into the ZeRO-3 profile."""
    cfg = configs.get_config("granite_3_2b")      # profile "fsdp", fsdp=False
    assert cfg.sharding_profile == "fsdp" and not cfg.fsdp
    rules = default_rules(MESH, cfg)
    assert rules.rules["mlp"] == "model"          # TP kept
    assert rules.rules["vocab"] == "model"
    assert rules.rules["embed"] is None           # no FSDP param sharding
    # with the opt-in, the same config takes the full ZeRO-3 profile
    import dataclasses
    cfg2 = dataclasses.replace(cfg, fsdp=True)
    rules2 = default_rules(MESH, cfg2)
    assert rules2.rules["embed"] == ("data", "model")
    assert rules2.rules["mlp"] is None
    # serving never takes the train-only ZeRO profile
    rules3 = default_rules(MESH, cfg2, serve=True)
    assert rules3.rules["mlp"] == "model"


def test_fsdp_rules_direct():
    """_fsdp_rules unit contract: params + batch over (data, model) jointly,
    no TP anywhere, pod left as pure gradient-replica DP."""
    cfg = configs.get_config("deepseek_67b")
    rules = _fsdp_rules(MESH, cfg)
    assert rules.rules["batch"] == ("data", "model")
    assert rules.rules["embed"] == ("data", "model")
    assert rules.rules["moe_groups"] == ("data", "model")
    for name in ("heads", "kv_heads", "mlp", "vocab", "experts", "rnn",
                 "q_proj", "kv_proj", "kv_cache_seq", "seq"):
        assert rules.rules[name] is None, name
    # pod axis untouched on the 3-axis mesh (pure replica DP)
    rules3 = _fsdp_rules(MESH3, cfg)
    assert rules3.rules["batch"] == ("data", "model")
    # divisibility fallback still applies: embed dim not divisible by 256
    assert rules.spec_for(("embed", "mlp"), (100, 22016)) == P(None, None)
    # one-axis mesh degrades to a scalar axis entry
    mesh1 = fake_mesh((8,), ("data",))
    assert _fsdp_rules(mesh1, cfg).rules["embed"] == "data"


def test_vocab_pad_for():
    assert vocab_pad_for(MESH) == 16
    assert vocab_pad_for(MESH3) == 16
    assert vocab_pad_for(fake_mesh((8,), ("data",))) == 1  # no model axis


@pytest.mark.parametrize("mesh_shape,axes,b,want,idle", [
    ((2, 2), ("data", "model"), 4, P("data", "model"), None),
    ((4,), ("model",), 4, P(None, "model"), None),
    # batch 1 cannot split over data: every data replica computes it whole
    ((2, 2), ("data", "model"), 1, P(None, "model"), "data"),
    # 8 kv heads on a 16-way model axis: every model shard computes all heads
    ((16, 16), ("data", "model"), 32, P("data", None), "model"),
])
def test_per_shard_kernel_spec(mesh_shape, axes, b, want, idle):
    """A Pallas attention kernel on a mesh splits over the rules' own batch
    and head axes; a mesh axis it cannot use is recorded and warned."""
    import warnings
    from repro.distributed.sharding import _kernel_spec
    cfg = configs.get_config("granite_3_2b")           # 32 q / 8 kv heads
    rules = default_rules(fake_mesh(mesh_shape, axes), cfg, serve=True)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        spec = _kernel_spec(rules, (b, 32, 256, 64), (b, 8, 256, 64))
    assert spec == want
    if idle is None:
        assert not caught and "per_shard_attention" not in rules.fallbacks
    else:
        assert len(caught) == 1 and idle in str(caught[0].message)
        assert idle in rules.fallbacks["per_shard_attention"]


def test_all_archs_build_rules_on_both_meshes():
    for name in configs.ARCHS:
        cfg = configs.get_config(name)
        for mesh in (MESH, MESH3):
            rules = default_rules(mesh, cfg)
            assert "batch" in rules.rules
