"""The pinned form of the resident pool (parallel/resident.py, 8-device
CPU mesh): ``to_pinned`` / ``from_pinned`` are the only code that knows
in which shape the image rows sit on the device, and every consumer reads
them back through ``pool_gather``.

On the CPU every layout is row-major, so what these tests can hold is bit
identity (same rows, same bytes, whatever the form), the accounting, and
that no program grows a pool-sized temporary.  What the TPU's compiler
does with the form is held by tests/test_chip_compile.py (a described
v5e) and chip_smoke.py (the chip).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import TinyClassifier

from active_learning_tpu.data.core import ArrayDataset, ViewSpec
from active_learning_tpu.data.synthetic import SYNTH_NORM
from active_learning_tpu.parallel import mesh as mesh_lib
from active_learning_tpu.parallel import resident as resident_lib
from active_learning_tpu.strategies import scoring
from active_learning_tpu.telemetry import profiler as profiler_lib
from active_learning_tpu.train.evaluation import make_eval_step

# CIFAR rows, the benchmark's rows, rows whose bytes divide by neither 128
# nor 4, one-channel rows (by 4, not by 128), rows that divide by 128 and
# not by a whole (8, 128) tile.
ROW_SHAPES = [(32, 32, 3), (224, 224, 3), (5, 7, 3), (28, 28, 1), (8, 16, 3)]
PINNED_TAILS = {(32, 32, 3): (24, 128), (224, 224, 3): (1176, 128),
                (5, 7, 3): (105,), (28, 28, 1): (784,), (8, 16, 3): (384,)}
LAYOUTS = ["replicated", "row"]


def _dataset(row_shape, n, seed=0):
    rng = np.random.default_rng(seed)
    images = rng.integers(0, 256, (n, *row_shape), dtype=np.uint8)
    targets = rng.integers(0, 4, n)
    return ArrayDataset(images, targets, 4, ViewSpec(SYNTH_NORM))


def _rows(row_shape):
    """224 px rows at 8 (one per device of the mesh), the rest at 96."""
    return 8 if row_shape == (224, 224, 3) else 96


@pytest.mark.parametrize("row_shape", ROW_SHAPES)
def test_form_follows_the_row_and_keeps_the_bytes(row_shape):
    rows = _dataset(row_shape, 8).images
    pinned = resident_lib.to_pinned(rows)
    assert pinned.shape == (8, *PINNED_TAILS[row_shape])
    assert pinned.shape == resident_lib.pinned_shape(rows.shape)
    assert pinned.dtype == rows.dtype and np.shares_memory(pinned, rows)
    back = resident_lib.from_pinned(pinned, row_shape)
    assert back.shape == rows.shape
    np.testing.assert_array_equal(back, rows)
    # Dimension 0 stays the row index: row i of the form is row i.
    assert pinned[3].tobytes() == rows[3].tobytes()


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_form_counts_elements_not_bytes(dtype):
    """A float row of 1,024 elements fills whole tiles like a uint8 row of
    1,024 bytes: the value is never reinterpreted."""
    rows = np.arange(4 * 8 * 32 * 4, dtype=dtype).reshape(4, 8, 32, 4)
    pinned = resident_lib.to_pinned(rows)
    assert pinned.shape == (4, 8, 128) and pinned.dtype == rows.dtype
    np.testing.assert_array_equal(
        resident_lib.from_pinned(pinned, (8, 32, 4)), rows)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("row_shape", ROW_SHAPES)
def test_gather_of_permuted_ids_is_the_host_rows(row_shape, layout):
    n = _rows(row_shape)
    ds = _dataset(row_shape, n)
    mesh = mesh_lib.make_mesh()
    images_dev, labels_dev = resident_lib.pool_arrays({}, ds, mesh,
                                                      sharding=layout)
    sharded = mesh_lib.is_row_sharded(images_dev)
    assert sharded == (layout == "row")
    assert images_dev.shape[1:] == PINNED_TAILS[row_shape]
    ids = np.random.default_rng(1).permutation(n)[:min(n, 16)].astype(np.int32)

    @jax.jit
    def gather(images, labels, idv):
        return resident_lib.pool_gather(images, idv, mesh, row_shape,
                                        labels=labels, sharded=sharded)

    img, lab = gather(images_dev, labels_dev, jnp.asarray(ids))
    assert img.shape == (len(ids), *row_shape) and img.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(img), ds.images[ids])
    np.testing.assert_array_equal(np.asarray(lab),
                                  ds.targets[ids].astype(np.int32))
    # Images alone (the scoring runners' call) take the same path.
    alone = jax.jit(lambda im, idv: resident_lib.pool_gather(
        im, idv, mesh, row_shape, sharded=sharded))(images_dev,
                                                    jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(alone), ds.images[ids])


@pytest.mark.parametrize("row_shape", ROW_SHAPES)
def test_row_sharded_batches_equal_replicated_batches(row_shape):
    n = _rows(row_shape)
    ds = _dataset(row_shape, n, seed=3)
    mesh = mesh_lib.make_mesh()
    ids = jnp.asarray(
        np.random.default_rng(2).permutation(n)[:8].astype(np.int32))
    got = {}
    for layout in LAYOUTS:
        images_dev, _ = resident_lib.pool_arrays({}, ds, mesh,
                                                 sharding=layout)
        got[layout] = jax.jit(
            lambda im, idv, sh=(layout == "row"): resident_lib.pool_gather(
                im, idv, mesh, row_shape, sharded=sh))(images_dev, ids)
    assert got["row"].sharding.is_equivalent_to(got["replicated"].sharding,
                                                got["row"].ndim)
    assert np.asarray(got["row"]).tobytes() \
        == np.asarray(got["replicated"]).tobytes()


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("row_shape", ROW_SHAPES)
def test_update_rows_block_reads_back_equal(row_shape, layout):
    n = max(_rows(row_shape), resident_lib.UPDATE_BLOCK_FLOOR)
    ds = _dataset(row_shape, n, seed=4)
    mesh = mesh_lib.make_mesh()
    cache = {}
    resident_lib.pool_arrays(cache, ds, mesh, sharding=layout)
    assert resident_lib.prewarm_update(cache, ds, mesh)
    lo, hi = n - 24, n - 3
    ds.images[lo:hi] = np.random.default_rng(5).integers(
        0, 256, ds.images[lo:hi].shape, dtype=np.uint8)
    assert resident_lib.update_rows(cache, ds, mesh, lo, hi)
    images_dev = cache["images"][(id(ds.images), n)][1]
    assert mesh_lib.is_row_sharded(images_dev) == (layout == "row")
    np.testing.assert_array_equal(
        resident_lib.from_pinned(np.asarray(images_dev)[:n], row_shape),
        ds.images)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("row_shape", ROW_SHAPES)
def test_rows_and_bytes_are_what_the_devices_hold(row_shape, layout):
    n = _rows(row_shape) - (1 if row_shape != (224, 224, 3) else 0)
    ds = _dataset(row_shape, n)
    mesh = mesh_lib.make_mesh()
    ndev = mesh.devices.size
    cache = {}
    images_dev, _ = resident_lib.pool_arrays(cache, ds, mesh,
                                             sharding=layout)
    held = n if layout == "replicated" else -(-n // ndev)
    (per_dev,) = resident_lib.rows_per_device(cache)
    assert per_dev == {str(d.id): held for d in mesh.devices.flat}
    on_device = max(s.data.nbytes for s in images_dev.addressable_shards)
    assert resident_lib.pinned_bytes(cache) == on_device \
        == held * int(np.prod(row_shape))
    # The budget admits exactly what the devices then hold.
    ways = 1 if layout == "replicated" else ndev
    assert resident_lib.eligible(ds, on_device, cache={}, shard_ways=ways)
    assert not resident_lib.eligible(ds, on_device - 1, cache={},
                                     shard_ways=ways)


# -- the check that the form holds --------------------------------------------

def _toy_runner(kind, layout, row_shape=(32, 32, 3), n=4096, batch=16):
    """A resident runner over a toy pool large beside its step's own
    temporaries (CIFAR rows: the tiled form)."""
    ds = _dataset(row_shape, n)
    mesh = mesh_lib.make_mesh()
    model = TinyClassifier(num_classes=4)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, *row_shape)), train=False)
    cache = {}
    images, labels = resident_lib.pool_arrays(cache, ds, mesh,
                                              sharding=layout)
    sharded = mesh_lib.is_row_sharded(images)
    small = (jnp.zeros((batch,), jnp.int32), jnp.ones((batch,), jnp.float32))
    if kind == "score":
        step = scoring.make_prob_stats_step(model, ds.view)
        run = resident_lib.get_runner(cache, step, mesh,
                                      scoring._runner_name(step), row_shape,
                                      sharded=sharded)
        return run, (variables, images, *small), images
    run = resident_lib.get_runner(
        cache, make_eval_step(model, ds.view, 4), mesh, "run_eval",
        row_shape, with_labels=True, sharded=sharded)
    return run, (variables, images, labels, *small), images


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("kind", ["score", "eval"])
def test_runners_read_the_pool_in_place(kind, layout):
    run, args, images = _toy_runner(kind, layout)
    got = resident_lib.assert_pool_read_in_place(run, args, pool_arg=1)
    assert got["pool_bytes"] == max(
        s.data.nbytes for s in images.addressable_shards)
    assert got["temp_bytes"] < got["pool_bytes"] // 4


@pytest.mark.parametrize("creep", ["cast", "copy"])
def test_a_pool_sized_operation_in_the_gather_is_caught(creep):
    """What the check exists to catch off the chip: a cast or a re-layout
    of the WHOLE pool in front of the gather."""
    _, args, _ = _toy_runner("score", "replicated")

    def run(variables, images, ids, mask):
        if creep == "cast":
            rows = images.astype(jnp.float32)[ids]
        else:
            rows = jnp.swapaxes(images, 1, 2)[ids]
        return rows.sum() + mask.sum()

    with pytest.raises(AssertionError, match="in place"):
        resident_lib.assert_pool_read_in_place(jax.jit(run), args,
                                               pool_arg=1)


@pytest.mark.parametrize("line,want", [
    ("  %copy.7 = u8[32768,224,224,3]{2,1,3,0:T(8,128)(4,1)} copy(%images.1), "
     "metadata={op_name=\"images\"}", [("copy.7", "copy", 32768 * 150528)]),
    ("  %images.1 = u8[32768,1176,128]{2,1,0:T(8,128)(4,1)} parameter(102), "
     "sharding={replicated}", [("images.1", "parameter", 32768 * 150528)]),
    ("  ROOT %reshape.53 = u8[256,1176,128]{2,1,0:T(8,128)(4,1)S(1)} "
     "reshape(%transpose.52)", [("reshape.53", "reshape", 256 * 150528)]),
    # A tuple result counts every member.
    ("  %copy-start.4 = (f32[7,7,3,64]{3,1,2,0:T(8,128)S(1)}, "
     "f32[7,7,3,64]{3,1,2,0:T(8,128)}, u32[]{:S(2)}) copy-start(%k.1)",
     [("copy-start.4", "copy-start", 2 * 7 * 7 * 3 * 64 * 4 + 4)]),
    ("  %lt.0 = pred[256]{0:T(512)(128)(4,1)} compare(%a, %b), direction=LT",
     [("lt.0", "compare", 256)]),
    ("  %c.2 = bf16[7,7,3,64]{3,1,2,0:T(8,128)(2,1)S(1)} convert(%c.1)",
     [("c.2", "convert", 7 * 7 * 3 * 64 * 2)]),
    ("  %get-tuple-element.7 = u8[64,24,128]{2,1,0} get-tuple-element(%p), "
     "index=1", [("get-tuple-element.7", "get-tuple-element", 64 * 3072)]),
    ("%fused_computation (param_0.2: u8[32768,1176,128]) -> u8[256,1176,128] {",
     []),
    ("ENTRY %main.16 (variables.1: f32[64]) -> f32[256] {", []),
])
def test_hlo_text_instructions(line, want):
    assert profiler_lib.hlo_text_instructions(line) == want
