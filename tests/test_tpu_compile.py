"""The three Pallas histogram kernels COMPILE for the v5e — checked without one.

libtpu ships a compile-only topology: ``get_topology_desc("v5e:2x2")``
returns four ``TPU v5 lite`` devices that can be lowered and compiled for
(XLA:TPU + Mosaic) but not executed on, with ``JAX_PLATFORMS=cpu``. A
histogram call handed a mesh over those devices lowers for them — kernel
choice, ``interpret=`` and the Mosaic VMEM ceiling follow the mesh's
device, not the process's default backend (ops/histogram.py
``_target_device``) — so tier-1 sees whether a kernel still compiles,
one-chip and ``shard_map``-sharded, aligned and ragged.

No skip when the topology is unavailable: this installation has libtpu, so
a missing one is a failure.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mmlspark_tpu.ops import histogram as H

D = 64
# rows: a multiple of the 512-row chunk on every shard / nothing of the kind
ALIGNED, RAGGED = 8192, 5004


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    assert len(devices) == 4 and devices[0].device_kind == "TPU v5 lite"
    # a compile-only client can serialize an executable but not read one
    # back, so persistent-cache entries for these programs would only ever
    # cost a write and a warning: keep them out of the cache
    key = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, key)
    jax.config.update(key, 1e9)
    yield devices
    jax.config.update(key, before)


def _spec(shape, dtype, mesh, *axes):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, P(*axes))
    )


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("n", [ALIGNED, RAGGED], ids=["aligned", "ragged"])
@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "shard_map"])
@pytest.mark.parametrize(
    "num_bins,kernel", [(256, "_hist_split_kernel"), (64, "_hist_kernel")]
)
def test_plane_kernels_compile_for_v5e(v5e, num_bins, kernel, chips, n):
    mesh = Mesh(np.array(v5e[:chips]), ("data",))
    assert H._use_split(num_bins) == (kernel == "_hist_split_kernel")
    text = _compiled_text(
        lambda b, s: H.plane_histogram(
            b, s, num_bins=num_bins, mesh=mesh, shard_axis="data"
        ),
        _spec((n, D), jnp.uint8, mesh, "data", None),
        _spec((n, 3), jnp.float32, mesh, "data", None),
    )
    assert "tpu_custom_call" in text
    # the sharded program sums the per-shard planes with an explicit psum
    assert ("all-reduce" in text) == (chips > 1)


# 32 slots x 64 features is the shape whose resident set overflowed Mosaic's
# default VMEM ceiling (see _VMEM_LIMIT_MB): it must keep compiling. Two of
# the four layout x shape pairs — this is the slowest kernel to compile
@pytest.mark.parametrize(
    "chips,n", [(1, ALIGNED), (4, RAGGED)],
    ids=["one_chip-aligned", "shard_map-ragged"],
)
def test_multi_plane_kernel_compiles_for_v5e(v5e, chips, n):
    mesh = Mesh(np.array(v5e[:chips]), ("data",))
    text = _compiled_text(
        lambda b, s, sl: H.multi_plane_histogram(
            b, s, sl, 32, mesh=mesh, shard_axis="data"
        ),
        _spec((n, D), jnp.uint8, mesh, "data", None),
        _spec((n, 3), jnp.float32, mesh, "data", None),
        _spec((n,), jnp.int32, mesh, "data"),
    )
    assert "tpu_custom_call" in text
    assert ("all-reduce" in text) == (chips > 1)


def test_default_backend_stays_cpu_while_lowering_for_tpu(v5e):
    """The process default is untouched: the same call with no mesh still
    takes the CPU lowering, and the v5e target gets Mosaic with the device
    kind's VMEM ceiling rather than the interpreter."""
    assert jax.default_backend() == "cpu"
    assert H.hist_lowering() in ("cpu", "scatter")
    mesh = Mesh(np.array(v5e[:1]), ("data",))
    assert H.hist_lowering(mesh) == "pallas"
    kw = H._pallas_call_kwargs(H._target_device(mesh))
    assert kw["interpret"] is False
    assert kw["compiler_params"].vmem_limit_bytes == 96 << 20


@pytest.mark.parametrize(
    "policy,voting,chips,kind,vector_split",
    [
        ("lossguide", False, 1, "partitioned", True),
        ("lossguide", False, 4, "masked", True),
        ("depthwise", False, 1, "depthwise", True),
        ("depthwise", False, 4, "depthwise", True),
        ("lossguide", True, 4, "voting", True),
        ("lossguide", True, 1, "partitioned", True),
    ],
    ids=["lossguide-one_chip", "lossguide-four_chips", "depthwise-one_chip",
         "depthwise-four_chips", "voting-four_chips",
         "voting-one_chip-falls-back"],
)
def test_the_rule_reads_a_tpu_mesh(v5e, policy, voting, chips, kind, vector_split):
    """``choose_grower``'s rows for the chip (its CPU rows are in
    tests/test_gbdt.py): the lowering is read off the mesh's device, not
    the process's default backend, and nothing in the environment."""
    from mmlspark_tpu.models.gbdt.treegrow import Grower, choose_grower

    mesh = Mesh(np.array(v5e[:chips]), ("data",))
    assert choose_grower(policy, voting=voting, mesh=mesh, shard_axis="data") == Grower(
        kind, "pallas", mesh, "data", sibling_subtract=True,
        vector_split=vector_split,
    )


def test_partitioned_grower_compiles_for_one_v5e_chip(v5e):
    """The one-chip leaf-wise grower as the chip gets it: uint8 bins, every
    bucket of both switches with its own Mosaic kernel, the gathers through
    ``order`` and the scatter that partitions a bucket (XLA:TPU refuses
    what the interpreter lets through). One kernel for the root and one
    per bucket."""
    from mmlspark_tpu.models.gbdt import treegrow

    mesh = Mesh(np.array(v5e[:1]), ("data",))
    grower = treegrow.choose_grower(mesh=mesh, shard_axis="data")
    assert grower.kind == "partitioned"
    n, d = RAGGED, 28
    sizes = treegrow._range_sizes(n)

    def grow(b, g, h, w):
        return treegrow.grow_tree(
            b, g, h, w, num_leaves=15, lambda_l2=0.0, min_gain=0.0,
            learning_rate=0.1, feature_mask=jnp.ones((d,), jnp.float32),
            min_data_in_leaf=0, min_sum_hessian=100.0, grower=grower,
        )

    rows = _spec((n,), jnp.float32, mesh)
    text = _compiled_text(grow, _spec((n, d), jnp.uint8, mesh), rows, rows, rows)
    assert text.count("tpu_custom_call") == 1 + len(sizes)
    assert "all-reduce" not in text


@pytest.mark.parametrize("h,f,experts,k,router", [
    (2048, 1792, 32, 4, "sigmoid"), (2048, 768, 128, 8, "softmax")],
    ids=["lfm2_8b_a1b", "keye_vl2_30b_a3b"])
def test_expert_layer_compiles_for_one_v5e_chip_at_published_widths(
        v5e, monkeypatch, h, f, experts, k, router):
    """The sparse expert layer of both language-model cells — 32 experts of
    2048 x 1792, top-4 by the sigmoid router; 128 of 2048 x 768, top-8 by the
    softmax router; a batch of 32,768 tokens — compiles for one chip: the
    three grouped products are two calls of the Mosaic kernel ``expert_gmm``
    under the scope ``lm.moe.experts`` (the up-call holds the gate; no
    ``ragged-dot`` of the TPU compiler's is left, and neither of the two
    routed x f products exists as an array), and the layer's temporaries stay
    far under the 6 GB the weights leave."""
    from jax.sharding import SingleDeviceSharding

    from mmlspark_tpu.ops import moe

    # the layer asks the histogram kernels' rule which device it lowers for:
    # a CPU process that compiles for a described chip answers for the chip
    monkeypatch.setattr(H, "_target_device", lambda mesh=None: v5e[0])
    one = SingleDeviceSharding(v5e[0])
    tokens = 32_768

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def layer(u, gate, bias, w1, w3, w2):
        idx, weights = (moe.route(u, gate, bias, k) if router == "sigmoid"
                        else moe.route_softmax(u, gate, k))
        return moe.expert_ffn(u, idx, weights, w1, w3, w2, experts)

    compiled = jax.jit(layer).lower(
        spec((tokens, h), jnp.bfloat16), spec((h, experts), jnp.float32),
        spec((experts,), jnp.float32), spec((experts, h, f), jnp.bfloat16),
        spec((experts, h, f), jnp.bfloat16), spec((experts, f, h), jnp.bfloat16)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(calls) == 2 and all("lm.moe.experts" in ln and "expert_gmm" in ln for ln in calls)
    assert "ragged-dot" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


@pytest.mark.parametrize("rows,keys", [(1, 32_768), (8, 4_096)], ids=["one_row", "eight_rows"])
def test_sparse_attention_compiles_for_one_v5e_chip_at_published_widths(v5e, rows, keys):
    """A block of 256 queries of the long-document cell against a batch's
    32,768 keys — an indexer of 16 heads of 64, 32 query heads over 4
    key/value heads of 128 — compiles for one chip: the attention over the
    selection is a Mosaic kernel, the index scores and the selection XLA's,
    and neither the indexer's ``Q x J x K`` products (the compiler folds
    the ReLU and the weighted sum into the product) nor the heads'
    ``Q x K`` scores exist as arrays: a block's temporaries stay at a few
    copies of its 33.5 MB of scores."""
    from jax.sharding import SingleDeviceSharding

    from mmlspark_tpu.ops import sparse_attention as sa

    one = SingleDeviceSharding(v5e[0])
    call = H._pallas_call_kwargs(v5e[0])
    assert call["interpret"] is False

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def block(qi, ki, w, q, k, v, reach):
        causal = (reach - 255 + jnp.arange(256))[:, None] >= jnp.arange(keys)[None, :]
        mask = sa.select(sa.index_scores(qi, ki, w), causal, 2048)
        return sa.attend_kernel(q, k, v, mask, reach, **call)

    compiled = jax.jit(block).lower(
        spec((rows, 16, 256, 64), jnp.bfloat16), spec((rows, keys, 64), jnp.bfloat16),
        spec((rows, 16, 256), jnp.float32), spec((rows, 4, 8, 256, 128), jnp.bfloat16),
        spec((rows, 4, keys, 128), jnp.bfloat16), spec((rows, 4, keys, 128), jnp.bfloat16),
        spec((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "sparse_attend" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


def test_held_expert_layer_compiles_for_one_v5e_chip_at_published_widths(v5e, monkeypatch):
    """One chip's share of the ``deepseek_v2`` expert layer — the
    group-limited router over 160, 20 experts of 5120 x 1536 held, a batch of
    16,384 tokens — compiles for one chip: the products are two calls of
    ``expert_gmm`` inside the share's device loop, over blocks of 24,576
    gathered rows and not the 98,304 pairs there are (``tiling`` narrows the
    up-call's block: its two matrices are 31.5 MB), and the layer's
    temporaries stay under 2 GB."""
    from jax.sharding import SingleDeviceSharding

    from mmlspark_tpu.ops import moe

    monkeypatch.setattr(H, "_target_device", lambda mesh=None: v5e[0])
    one = SingleDeviceSharding(v5e[0])
    tokens, h, f, experts, held, k = 16_384, 5120, 1536, 160, 20, 6

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def layer(u, gate, w1, w3, w2):
        idx, weights = moe.route_group_limited(u, gate, k, 8, 3, False, 16.0)
        return moe.expert_ffn(u, idx, weights, w1, w3, w2, experts, (0, held))

    compiled = jax.jit(layer).lower(
        spec((tokens, h), jnp.bfloat16), spec((h, experts), jnp.float32),
        spec((held, h, f), jnp.bfloat16), spec((held, h, f), jnp.bfloat16),
        spec((held, f, h), jnp.bfloat16)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(calls) == 2 and all("lm.moe.experts" in ln and "expert_gmm" in ln for ln in calls)
    assert all("bf16[24576," in ln for ln in calls) and "ragged-dot" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


@pytest.mark.parametrize("rows,keys", [(1, 16_384), (8, 2_048)], ids=["one_row", "eight_rows"])
def test_latent_attention_compiles_for_one_v5e_chip_at_published_widths(v5e, rows, keys):
    """A batch of the ``deepseek_v2`` cell — 128 heads of 128 + 64 score and
    128 value dimensions, one row of 16,384 or eight of 2,048 — compiles for
    one chip: the causal pairs are one Mosaic kernel that takes the heads' own
    keys and the shared rotated key as separate operands, and no score
    tensor exists as an array (the temporaries are the one copy the compiler
    makes of a 64-wide operand into its padded layout)."""
    from jax.sharding import SingleDeviceSharding

    from mmlspark_tpu.ops import latent_attention as la

    one = SingleDeviceSharding(v5e[0])
    call = H._pallas_call_kwargs(v5e[0])

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(lambda *a: la.attend_kernel(*a, **call)).lower(
        spec((rows, 128, keys, 128)), spec((rows, 128, keys, 64)), spec((rows, 128, keys, 128)),
        spec((rows, keys, 64)), spec((rows, 128, keys, 128)), spec((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "latent_attend" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


@pytest.mark.parametrize("tokens,h,vocab", [
    (32_768, 2048, 65_536), (32_768, 2048, 151_936), (16_384, 5120, 12_800)],
    ids=["lfm2_8b_a1b", "keye_vl2_30b_a3b", "deepseek_v2"])
def test_head_compiles_for_one_v5e_chip_at_published_widths(v5e, monkeypatch, tokens, h, vocab):
    """The head of the three language-model cells — a batch's 32,768 (16,384)
    final states against 65,536 tied ids, 151,936 untied ones (1,187 x 128:
    a partial last tile) and the held slice of 12,800 at a width of 5,120 —
    compiles for one chip as the one Mosaic kernel ``head_logprobs`` under
    ``lm.head``: it reads the matrix where it lies (no padded or transposed
    copy: the program's temporaries are a few kilobytes) and no
    ``tokens x vocabulary`` logits exist as an array."""
    from jax.sharding import SingleDeviceSharding

    from mmlspark_tpu.models import causal_lm as lm

    monkeypatch.setattr(H, "_target_device", lambda mesh=None: v5e[0])
    one = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def head(matrix, u, targets, work):
        with jax.named_scope("lm.head"):
            return lm.head_logprobs(matrix, u, targets, lm.HEAD_BLOCK, work)

    compiled = jax.jit(head).lower(
        spec((vocab, h), jnp.bfloat16), spec((tokens, h), jnp.bfloat16),
        spec((tokens,), jnp.int32), spec((tokens,), jnp.bool_)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(calls) == 1 and "lm.head" in calls[0] and "head_logprobs" in calls[0]
    assert f"bf16[{vocab},{h}]" in calls[0]           # the matrix as it was handed in
    assert f",{vocab}]" not in text.replace(f"bf16[{vocab},{h}]", "")    # no logits
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6
