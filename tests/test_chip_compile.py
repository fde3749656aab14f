"""The accelerator forms of the main path, compiled for a DESCRIBED TPU v5e.

No chip is attached here: the TPU compiler that ships with jax compiles for
a ``v5e:2x2`` topology that is described, not present, so what it would
refuse on the chip (a shape it cannot tile, a program over the 16 GB of
HBM, a collective it cannot partition) it refuses in this file at no chip
time.  Nothing runs, so these tests say nothing about results or speed —
``chip_smoke.py`` is the run.

What is compiled is what the chip executes on the two protocol shapes
(``chip_smoke.py`` phases a-c and its four-chip phase), built through the
repo's own builders on ``make_mesh(devices=<described devices>)``:

  * the SSLResNet50/224 bf16 train step (custom VJPs of ops/backward.py,
    fused SGD) at 128 rows, in the chained form the host feed dispatches;
  * the SSLResNet18/32 epoch scan over a resident 50,000-row pool, on one
    device and on a four-device mesh with the pool row-sharded (the
    gradient all-reduce must be in the program);
  * the 224 px scoring step at the accelerator floor of 256 rows, as the
    gather runner over a pinned pool;
  * the batched k-center scan over ``[50000, 2048]``;
  * the A.X-K1 expert layer at its published widths.

``dtype="auto"`` reads the live backend and would pick float32 here, so the
tests name bfloat16 themselves.  The topology is described inside a
module-scoped fixture — never at import time: only one process may hold the
TPU library, and every xdist worker imports every test file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from active_learning_tpu.data.core import CIFAR10_NORM, IMAGENET_NORM, ViewSpec
from active_learning_tpu.experiment.arg_pools import get_train_config
from active_learning_tpu.models.factory import get_network
from active_learning_tpu.parallel import mesh as mesh_lib
from active_learning_tpu.parallel import resident as resident_lib
from active_learning_tpu.pool import bucket_size
from active_learning_tpu.strategies import kcenter, scoring
from active_learning_tpu.train.evaluation import make_eval_step
from active_learning_tpu.train.trainer import Trainer, TrainState, num_batches

HBM_BYTES = 16 * 10**9  # one v5e chip
POOL_ROWS = 50_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next one warns and
    recompiles) — keep the cache off around this module."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    return mesh_lib.make_mesh(devices=topo.devices[:1])


@pytest.fixture(scope="module")
def four_chips(topo, no_compile_cache):
    assert len(topo.devices) == 4
    return mesh_lib.make_mesh(devices=topo.devices)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state_spec(trainer, image_shape):
    """The TrainState the driver would hand the step, as shapes placed
    where ``Trainer.init_state`` places the arrays (replicated)."""
    rep = mesh_lib.replicated_sharding(trainer.mesh)

    def init(rng):
        variables = trainer.model.init(
            rng, jnp.zeros((1, *image_shape), jnp.float32), train=False)
        return TrainState(params=variables["params"],
                          batch_stats=variables.get("batch_stats", {}),
                          opt_state=trainer._opt_init(variables["params"]),
                          step=jnp.zeros((), jnp.int32))

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    return jax.tree.map(lambda s: _spec(s.shape, s.dtype, rep), shapes)


def _trainer(dataset, model_name, mesh, pool_sharding="auto"):
    model = get_network(dataset, model_name, dtype="bfloat16")
    cfg = dataclasses.replace(get_train_config("default", dataset),
                              pool_sharding=pool_sharding)
    assert cfg.loader_tr.batch_size == 128
    return Trainer(model, cfg, mesh, num_classes=model.num_classes)


def _pinned_spec(rows, row_shape, sharding):
    return _spec(resident_lib.pinned_shape((rows, *row_shape)), jnp.uint8,
                 sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_resnet50_224_bf16_train_step_128_rows(one_chip):
    trainer = _trainer("imagenet", "SSLResNet50", one_chip)
    assert trainer.fused_tx is not None  # the fused SGD path
    rep = mesh_lib.replicated_sharding(one_chip)
    rows = mesh_lib.batch_sharding(one_chip)
    bs = trainer.padded_batch_size(128)
    batch = {"image": _spec((bs, 224, 224, 3), jnp.uint8, rows),
             "label": _spec((bs,), jnp.int32, rows),
             "index": _spec((bs,), jnp.int32, rows),
             "mask": _spec((bs,), jnp.float32, rows)}
    compiled = trainer._chained_train_step.lower(
        _state_spec(trainer, (224, 224, 3)), batch,
        _spec((2,), jnp.uint32, rep), _spec((), jnp.float32, rep),
        _spec((1000,), jnp.float32, rep),
        view=ViewSpec(IMAGENET_NORM, augment=True, pad=0)).compile()
    # bf16 reached the convolutions (auto would have said f32 here).
    assert "bf16[" in compiled.as_text()
    # The step and phase a's pinned 4,096-row pool fit one chip together.
    pool = 4096 * 224 * 224 * 3
    assert _device_bytes(compiled) + pool < HBM_BYTES


def _epoch_scan_compiled(mesh, sharded: bool):
    trainer = _trainer("cifar10", "SSLResNet18", mesh,
                       pool_sharding="row" if sharded else "replicated")
    assert trainer.pool_sharding == ("row" if sharded else "replicated")
    rep = mesh_lib.replicated_sharding(mesh)
    pool = mesh_lib.row_sharding(mesh) if sharded else rep
    bs = trainer.padded_batch_size(128)
    steps = trainer.bucket_steps(num_batches(1000, bs))  # a 1000-row round
    return trainer._build_epoch_scan((32, 32, 3)).lower(
        _state_spec(trainer, (32, 32, 3)),
        _pinned_spec(POOL_ROWS, (32, 32, 3), pool),
        _spec((POOL_ROWS,), jnp.int32, pool),
        _spec((steps, bs), jnp.int32, rep),
        _spec((steps, bs), jnp.float32, rep),
        _spec((steps,), jnp.float32, rep),
        _spec((2,), jnp.uint32, rep), _spec((), jnp.float32, rep),
        _spec((10,), jnp.float32, rep),
        view=ViewSpec(CIFAR10_NORM, augment=True, pad=4),
        sharded=sharded).compile()


def test_resnet18_resident_epoch_scan_one_device(one_chip):
    compiled = _epoch_scan_compiled(one_chip, sharded=False)
    text = compiled.as_text()
    assert "while" in text and "bf16[" in text
    assert _device_bytes(compiled) < HBM_BYTES


def test_resnet18_resident_epoch_scan_four_devices_row_sharded(four_chips):
    compiled = _epoch_scan_compiled(four_chips, sharded=True)
    text = compiled.as_text()
    # The data-parallel contract: gradients (and the sharded gather's
    # owner sum) cross the mesh inside the one program.
    assert "all-reduce" in text
    # Each device holds a QUARTER of the pool rows, not the pool.
    quarter = f"u8[{POOL_ROWS // 4},24,128]"
    assert quarter in text and f"u8[{POOL_ROWS},24,128]" not in text
    assert _device_bytes(compiled) < HBM_BYTES


def _variables_spec(model, mesh):
    rep = mesh_lib.replicated_sharding(mesh)
    variables = jax.eval_shape(
        lambda rng: model.init(rng, jnp.zeros((1, 224, 224, 3), jnp.float32),
                               train=False), jax.random.PRNGKey(0))
    return jax.tree.map(lambda s: _spec(s.shape, s.dtype, rep), variables)


def test_224px_scoring_step_at_the_256_row_floor(one_chip):
    model = get_network("imagenet", "SSLResNet50", dtype="bfloat16")
    step = scoring.make_prob_stats_step(
        model, ViewSpec(IMAGENET_NORM, augment=False))
    run = resident_lib.get_runner({}, step, one_chip,
                                  scoring._runner_name(step), (224, 224, 3))
    rep = mesh_lib.replicated_sharding(one_chip)
    compiled = run.lower(
        _variables_spec(model, one_chip),
        _pinned_spec(4096, (224, 224, 3), rep),
        _spec((256,), jnp.int32, rep), _spec((256,), jnp.float32, rep)
    ).compile()
    assert "bf16[" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("runner,chips", [("score", 1), ("eval", 1),
                                          ("score", 4)])
def test_224px_runners_read_the_pinned_pool_in_place(runner, chips, one_chip,
                                                     four_chips):
    """The benchmark's pool (32,768 rows of 224 px a chip, 4.93 GB) under
    the v5e's own layout choice: no program re-lays it out or copies it.
    With the rows pinned as ``[N, 224, 224, 3]`` this fails on
    ``copy.7 = u8[32768,224,224,3]{2,1,3,0} copy(...{0,2,3,1})`` and 5.64
    GB of temporaries (PERF.md section 6, PR 26)."""
    mesh = one_chip if chips == 1 else four_chips
    rep = mesh_lib.replicated_sharding(mesh)
    pool = rep if chips == 1 else mesh_lib.row_sharding(mesh)
    rows, batch = 32_768 * chips, 256 * chips
    model = get_network("imagenet", "SSLResNet18", dtype="bfloat16")
    view = ViewSpec(IMAGENET_NORM, augment=False)
    small = (_spec((batch,), jnp.int32, rep), _spec((batch,), jnp.float32, rep))
    images = _pinned_spec(rows, (224, 224, 3), pool)
    if runner == "score":
        step = scoring.make_prob_stats_step(model, view)
        run = resident_lib.get_runner({}, step, mesh,
                                      scoring._runner_name(step),
                                      (224, 224, 3), sharded=chips > 1)
        args = (_variables_spec(model, mesh), images, *small)
    else:
        run = resident_lib.get_runner(
            {}, make_eval_step(model, view, model.num_classes), mesh,
            "run_eval", (224, 224, 3), with_labels=True)
        args = (_variables_spec(model, mesh), images,
                _spec((rows,), jnp.int32, pool), *small)
    got = resident_lib.assert_pool_read_in_place(run, args, pool_arg=1)
    assert got["pool_bytes"] == 32_768 * 224 * 224 * 3


def test_batched_kcenter_scan_50000_by_2048(one_chip):
    rep = mesh_lib.replicated_sharding(one_chip)
    n = bucket_size(POOL_ROWS, floor=kcenter.POOL_BUCKET_FLOOR)
    compiled = kcenter._kcenter_scan_batched.lower(
        (_spec((n, 2048), jnp.float32, rep),), _spec((n,), jnp.float32, rep),
        _spec((n,), jnp.float32, rep), _spec((n,), jnp.float32, rep),
        budget=1000, q=kcenter.DEFAULT_BATCH_Q).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_axk1_expert_layer_runs_its_held_experts_batched(one_chip):
    """The A.X-K1 expert layer at full widths (16 x 512 tokens, twelve held
    experts of 7168 -> 2048 -> 7168, tile 512): each projection is ONE
    matmul over all twelve held experts a chunk (``[12, 512, ...]``), no
    matmul takes one expert's tile of 512 rows as the parent's loop did
    (``[512, 2048]`` and ``[512, 7168]``: the trip that paid a 512-row
    scatter-add each, PERF.md section 5), and the temporaries stay under
    1 GB."""
    from active_learning_tpu.models import mla_moe
    cfg = mla_moe.AXK1_EP16_L7
    layer = mla_moe._Experts(cfg, jnp.bfloat16)
    rep = mesh_lib.replicated_sharding(one_chip)
    d = cfg.hidden_size
    params = jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, rep),
        jax.eval_shape(lambda k: layer.init(k, jnp.zeros((1, 8, d))),
                       jax.random.PRNGKey(0)))
    compiled = jax.jit(
        lambda p, x: layer.apply(p, x, mutable=["counters"])).lower(
        params, _spec((16, 512, d), jnp.float32, rep)).compile()
    shapes = set(re.findall(r"= f32\[([0-9,]+)\]\{[^}]*\} convolution\(",
                            compiled.as_text()))
    assert {"12,512,2048", "12,512,7168"} <= shapes
    assert not {"512,2048", "512,7168"} & shapes
    assert compiled.memory_analysis().temp_size_in_bytes < 10 ** 9


@pytest.mark.parametrize("devices", (1, 4))
@pytest.mark.parametrize("preset", ("AXK1_EP16_L7", "LONGCAT_FLASH_EP32_L4"))
def test_token_encoders_attention_is_one_fused_kernel(preset, devices,
                                                       one_chip, monkeypatch):
    """One attention call of each token-encoder cell at its published
    widths (64 heads, qk 192, v 128) over 16 x 512 rows, lowered for the
    TPU.  In a process of one device the causal core is the Mosaic kernel
    (ops/attention.py), and no float32 ``[16, 64, 512, 512]`` scores
    buffer is left in the module; in a process of four, which the compiler
    could not partition the kernel over, it is the einsum form, which
    writes them.  The call sows its ``attn_fused`` count, one entry a
    row."""
    from active_learning_tpu.models import mla_moe, shortcut_moe
    monkeypatch.setattr(jax, "device_count", lambda *a: devices)
    cfg = getattr(mla_moe, preset, None) or getattr(shortcut_moe, preset)
    rope = (shortcut_moe if preset.startswith("LONGCAT") else
            mla_moe).rope_tables
    layer = mla_moe._Attention(cfg, jnp.bfloat16)
    rep = mesh_lib.replicated_sharding(one_chip)
    d = cfg.hidden_size
    cos, sin = (_spec(a.shape, a.dtype, rep) for a in rope(cfg, 512))
    params = jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, rep),
        jax.eval_shape(lambda k, c, s: {"params": layer.init(
            k, jnp.zeros((1, 512, d)), c, s)["params"]},
            jax.random.PRNGKey(0), cos, sin))
    lowered = jax.jit(
        lambda p, x, c, s: layer.apply(p, x, c, s, mutable=["counters"])
    ).lower(params, _spec((16, 512, d), jnp.float32, rep), cos, sin)
    text = lowered.compile().as_text()
    fused = devices == 1
    assert ("tpu_custom_call" in text) == fused
    assert bool(re.search(r"f32\[16,64,512,512\]", text)) != fused
    counts = lowered.out_info[1]["counters"]["attn_fused"]
    assert [(c.shape, c.dtype) for c in counts] == [((16,), jnp.int32)]
