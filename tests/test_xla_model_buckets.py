"""What ``XLAModel`` gained for rows of unequal length — a batch size per
call, integer inputs with the lengths carried, weights that are already on
the device left where they are — and what it kept: ``ImageFeaturizer``'s
compiled shape and call sequence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu import obs
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.models import ImageFeaturizer
from mmlspark_tpu.models.xla_model import XLAModel
from mmlspark_tpu.parallel.mesh import get_mesh
from mmlspark_tpu.parallel.sharding import replicate


def _token_model(**kw):
    """Sums each row's ids up to its length (the trailing column)."""
    def apply_fn(vs, packed):
        assert packed.dtype == jnp.int32
        ids, lengths = packed[:, :-1], packed[:, -1]
        keep = jnp.arange(ids.shape[1])[None, :] < lengths[:, None]
        return (jnp.where(keep, ids, 0).sum(1) * vs["scale"]).astype(jnp.float32)

    m = XLAModel(input_col="tokens", output_col="sum", input_dtype=None, **kw)
    m.set(apply_fn=apply_fn, variables={"scale": jnp.ones((), jnp.int32)})
    return m


def _packed(rng, rows, length):
    lens = rng.integers(1, length + 1, rows)
    packed = rng.integers(1, 1000, (rows, length + 1)).astype(np.int32)
    packed[:, -1] = lens
    want = np.array([packed[r, :n].sum() for r, n in enumerate(lens)], np.float32)
    return packed, want


@pytest.mark.parametrize("length,batch", [(16, 32), (64, 8), (128, 8)])
def test_a_batch_size_per_call_is_a_compiled_shape_per_bucket(rng, length, batch):
    m = _token_model(batch_size=16)
    packed, want = _packed(rng, 2 * batch + 3, length)
    obs.clear_recent_spans()
    got = m.apply_batch(packed, batch_size=batch)
    np.testing.assert_array_equal(got, want)
    mesh = get_mesh()
    assert set(m._jit_cache) == {((batch, length + 1), id(mesh))}
    span = [s for s in obs.recent_spans() if s.name == "xla_model.apply_batch"][-1]
    # the call that built the bucket's program says so, and which shape
    assert span.attrs == {"rows": 2 * batch + 3, "batches": 3, "overlapped": False,
                          "program_new": True, "shape": [batch, length + 1]}
    # the stage's own batch size still serves a call that names none
    np.testing.assert_array_equal(m.apply_batch(packed), want)
    assert set(m._jit_cache) == {((batch, length + 1), id(mesh)), ((16, length + 1), id(mesh))}


def test_a_call_batch_is_rounded_up_to_the_mesh_like_the_stages_own(rng):
    m = _token_model(batch_size=16)
    packed, want = _packed(rng, 5, 8)
    np.testing.assert_array_equal(m.apply_batch(packed, batch_size=3), want)
    n_dev = get_mesh().devices.size
    assert [k[0][0] for k in m._jit_cache] == [-(-3 // n_dev) * n_dev]


def test_integer_inputs_reach_the_program_as_integers_only_when_asked(rng):
    packed, want = _packed(rng, 4, 8)
    np.testing.assert_array_equal(_token_model(batch_size=8).apply_batch(packed), want)
    floats = XLAModel(input_col="x", output_col="y", batch_size=8)   # the default: float32
    floats.set(apply_fn=lambda vs, x: x[:, :1] * 0 + (x.dtype == jnp.float32), variables={})
    assert bool(np.all(floats.apply_batch(packed) == 1.0))


def test_replicate_leaves_what_is_already_on_the_mesh_where_it_is():
    mesh = get_mesh()
    host = np.arange(12, dtype=np.float32).reshape(3, 4)
    placed = replicate({"w": host}, mesh)["w"]
    assert placed.is_fully_replicated and placed.sharding.device_set == set(mesh.devices.flat)
    again = replicate({"w": placed}, mesh)["w"]
    assert again is placed
    # an array on one device of a larger mesh is not "on the mesh": it is placed
    single = jax.device_put(host, mesh.devices.flat[0])
    if mesh.devices.size > 1:
        moved = replicate({"w": single}, mesh)["w"]
        assert moved is not single and moved.sharding.device_set == set(mesh.devices.flat)
    m = XLAModel(input_col="x", output_col="y", batch_size=8)
    m.set(apply_fn=lambda vs, x: x * vs["w"][0, 1], variables={"w": placed})
    assert m._device_variables(mesh)["w"] is placed
    np.testing.assert_allclose(m.apply_batch(np.ones((3, 2), np.float32)), np.ones((3, 2)))


def test_image_featurizer_compiled_shape_and_call_sequence_are_what_they_were():
    """A 3-batch partition: one compiled shape (batch, H, W, C) of uint8,
    three stage / dispatch pairs, no backpressure (four may be in flight),
    one drain, one concat — the sequence the ResNet cell measured."""
    def apply_fn(vs, x):
        assert x.dtype == jnp.float32   # the featurizer's resize casts on the device
        return {"pool": x.mean(axis=(1, 2)) * vs["scale"], "logits": x.sum(axis=(1, 2, 3))}

    stage = ImageFeaturizer(input_col="image", output_col="features", batch_size=8,
                            image_size=8, apply_fn=apply_fn,
                            variables={"scale": jnp.ones((3,), jnp.float32)})
    rows = 8 * 3
    images = np.random.default_rng(0).integers(0, 256, (rows, 8, 8, 3), dtype=np.uint8)
    df = DataFrame.from_dict({"image": images})
    stage.transform(df)["features"]  # warm: compiles
    inner = stage._build()
    assert set(inner._jit_cache) == {((8, 8, 8, 3), id(get_mesh()))}
    obs.clear_recent_spans()
    feats = stage.transform(df)["features"]
    assert feats.shape == (rows, 3) and feats.dtype == np.float32
    assert set(inner._jit_cache) == {((8, 8, 8, 3), id(get_mesh()))}
    names = [s.name for s in sorted(obs.recent_spans(), key=lambda s: s.wall_ns)
             if s.name.startswith(("featurize.", "xla_model."))]
    assert names == [
        "featurize.partition", "featurize.coerce", "xla_model.apply_batch",
        "xla_model.prepare", "xla_model.turn",
        "xla_model.stage", "xla_model.dispatch", "xla_model.stage", "xla_model.dispatch",
        "xla_model.stage", "xla_model.dispatch", "xla_model.drain", "xla_model.concat"]
    assert names.count("xla_model.backpressure") == 0
    staged = [s.attrs["bytes"] for s in obs.recent_spans() if s.name == "xla_model.stage"]
    assert staged == [8 * 8 * 8 * 3] * 3   # uint8 pixels, one byte each
