"""The flat-buffer kernels and the flat engine program, compiled for a
described TPU v5e chip (no chip attached): whatever the TPU compiler would
refuse on the chip, it refuses here.

Widths are those of ``chip_smoke.py`` phase (a): a 2^26-element fp32
buffer with b2=20 directions, and the AirComp reduce at M=10 over 2^24 and
M=50 over 2^22. Each test asserts that the compiled program holds the
Mosaic kernel (``tpu_custom_call``), i.e. that nothing fell back to the
interpreter.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import zo_aircomp as zac
from repro.kernels import zo_axpy as za

D = 2 ** 26
B2 = 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("acc", [None, "walk", "close"])
def test_zo_walk_compiles(one_chip, acc):
    """The walk alone, the walk carrying its aliased accumulator, and the
    closing accumulation alone."""
    x = u = ((D // 128, 128), jnp.float32)
    key, nn = ((2,), jnp.uint32), ((2,), jnp.int32)
    ab, e = ((2,), jnp.float32), ((1,), jnp.float32)
    if acc is None:
        fn = lambda x, k, nn, ab: za.zo_walk(x, k, nn, ab, interpret=False)
        shapes = (x, key, nn, ab)
    elif acc == "walk":
        fn = lambda x, k, nn, ab, u, e: za.zo_walk(x, k, nn, ab, (u, e),
                                                   interpret=False)
        shapes = (x, key, nn, ab, u, e)
    else:
        fn = lambda k, nn, u, e: za.zo_walk(None, k, nn, None, (u, e),
                                            interpret=False)
        shapes = (key, ((1,), jnp.int32), u, e)
    text = _compiled_text(fn, *shapes, sharding=one_chip)
    assert "tpu_custom_call" in text
    if acc is not None:
        assert "output_to_operand_aliasing" in text


def test_zo_replay_compiles(one_chip):
    text = _compiled_text(
        lambda x, k, c: za.zo_replay(x, k, c, interpret=False),
        ((D // 128, 128), jnp.float32), ((2,), jnp.uint32),
        ((B2,), jnp.float32), sharding=one_chip)
    assert "tpu_custom_call" in text


def test_zo_dirnorms_compiles(one_chip):
    text = _compiled_text(
        lambda k, d: za.zo_dirnorms(k, d, b2=B2, n_pad=D, interpret=False),
        ((2,), jnp.uint32), ((), jnp.int32), sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,n", [(10, 2 ** 24), (50, 2 ** 22)])
def test_aircomp_reduce_compiles(one_chip, m, n):
    """M=50 needs more than the default 16 MiB of scoped VMEM: the kernel
    must ask for its double-buffered block."""
    text = _compiled_text(
        lambda x, s, d: zac.aircomp_reduce(x, s, d, interpret=False),
        ((m, n // 128, 128), jnp.float32), ((m,), jnp.float32),
        ((1,), jnp.int32), sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["zo_walk", "zo_walk_acc", "zo_replay",
                                    "zo_dirnorms"])
def test_kernels_compile_vmapped_over_cohort(one_chip, kernel):
    """The flat round vmaps every kernel over the M cohort clients, which
    batches the scalar SMEM operands too (one key per client)."""
    m, rows = 10, 2 * za.BLOCK_ROWS
    x = ((m, rows, 128), jnp.float32)
    keys = ((m, 2), jnp.uint32)
    if kernel == "zo_walk":
        fn = jax.vmap(lambda x, k, ab: za.zo_walk(
            x, k, jnp.asarray([0, 1]), ab, interpret=False))
        shapes = (x, keys, ((m, 2), jnp.float32))
    elif kernel == "zo_walk_acc":
        fn = jax.vmap(lambda x, k, ab, u, e: za.zo_walk(
            x, k, jnp.asarray([0, 1]), ab, (u, e), interpret=False))
        shapes = (x, keys, ((m, 2), jnp.float32), x, ((m, 1), jnp.float32))
    elif kernel == "zo_replay":
        fn = jax.vmap(lambda x, k, c: za.zo_replay(x, k, c, interpret=False))
        shapes = (x, keys, ((m, B2), jnp.float32))
    else:
        fn = jax.vmap(lambda k: za.zo_dirnorms(
            k, rows * 128 - 5, b2=B2, n_pad=rows * 128, interpret=False))
        shapes = (keys,)
    assert "tpu_custom_call" in _compiled_text(fn, *shapes, sharding=one_chip)


def test_flat_experiment_compiles_with_kernels(one_chip, monkeypatch):
    """The whole flat-path experiment program (quickstart federation, 2
    rounds) compiles for the chip with its kernels inside. The kernels
    choose interpret mode from the default backend, which is the CPU here,
    so the test steers that choice to the compiled path."""
    from repro import sim
    from repro.kernels import ops
    from repro.workloads import neural

    monkeypatch.setattr(ops, "_auto_interpret",
                        lambda i: False if i is None else i)
    task = neural.make_task("softmax", n_train=6000, n_test=1000,
                            n_clients=50, partition="shards")
    cfg = neural.default_config(task, n_participating=10, lr=1e-3,
                                weight_by_size=False, flat_params=True)
    fn = sim.make_experiment_fn(task.loss, cfg, 2)

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = sds(jax.eval_shape(lambda: task.init(0)))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    text = fn.lower(params, None, key, None, None, None,
                    sds(task.store)).compile().as_text()
    assert "tpu_custom_call" in text


def test_scopes_and_kernel_names_in_compiled_program(one_chip, monkeypatch):
    """The names a chip trace is read by survive the chip's compiler: the
    program's registered scopes on the instructions' ``op_name`` paths,
    and the kernel instruction names the trace reducer keys on. The flat
    AirComp experiment with a fading channel and an in-scan eval runs
    every registered scope and the flat path's three kernels; the
    update rides the walk, so no zo_replay op is left."""
    import re

    from repro import sim
    from repro.kernels import ops
    from repro.obs.trace import SCOPES
    from repro.workloads import neural

    monkeypatch.setattr(ops, "_auto_interpret",
                        lambda i: False if i is None else i)
    task = neural.make_task("softmax", n_train=6000, n_test=1000,
                            n_clients=50, partition="shards")
    cfg = neural.default_config(
        task, n_participating=10, lr=1e-3, flat_params=True, aircomp=True,
        channel_model=sim.ChannelModel.from_doppler(0.02))

    def eval_fn(p):
        return {"test_loss": task.loss(p, task.test)}

    fn = sim.make_experiment_fn(task.loss, cfg, 2, eval_fn=eval_fn,
                                eval_every=1)

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = sds(jax.eval_shape(lambda: task.init(0)))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    cstate = sds(jax.eval_shape(
        lambda: cfg.channel_model.init_state(50, jax.random.key(0))))
    text = fn.lower(params, None, key, None, cstate, None,
                    sds(task.store)).compile().as_text()
    for name in SCOPES:
        assert f"{name}/" in text or f"({name})" in text, name
    instrs = set(re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ", text,
                            re.MULTILINE))
    bases = {re.sub(r"\.\d+$", "", n) for n in instrs}
    for kernel in ("zo_walk", "zo_dirnorms", "aircomp_reduce"):
        assert kernel in bases, kernel
    # the update rides the walk: no zo_replay op on the flat local path
    assert "zo_replay" not in bases


def test_flat_experiment_kernels_sized_to_d(one_chip, monkeypatch):
    """With flat_block_rows=0 every kernel of the flat AirComp experiment
    at the softmax model's d = 7,850 runs on the geometry sized from d:
    [64, 128] blocks (n_pad 8,192), and no kernel operand keeps the
    512-row, 65,536-element block."""
    import re

    from repro import sim
    from repro.kernels import ops
    from repro.workloads import neural

    monkeypatch.setattr(ops, "_auto_interpret",
                        lambda i: False if i is None else i)
    task = neural.make_task("softmax", n_train=6000, n_test=1000,
                            n_clients=50, partition="shards")
    cfg = neural.default_config(task, n_participating=10, lr=1e-3,
                                flat_params=True, flat_block_rows=0,
                                aircomp=True)
    fn = sim.make_experiment_fn(task.loss, cfg, 2)

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = sds(jax.eval_shape(lambda: task.init(0)))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    text = fn.lower(params, None, key, None, None, None,
                    sds(task.store)).compile().as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    names = {re.sub(r"\.\d+$", "", re.search(r"%([\w.\-]+) = ", l)[1])
             for l in calls}
    assert names == {"zo_walk", "zo_dirnorms", "aircomp_reduce"}, names
    blocked = [rows for l in calls
               for rows in re.findall(r"f32\[(?:\d+,)*?(\d+),128\]", l)]
    assert blocked and set(blocked) == {"64"}, blocked
    assert not any("65536" in l or "512,128" in l for l in calls)


def test_fedavg_cnn_experiment_at_published_widths(one_chip, monkeypatch):
    """FedAvg's CNN (d = 1,663,370) on the flat-kernel plan, 2 rounds of
    the paper's federation, compiles for the chip: every kernel is Mosaic,
    its operands are [M, 13,104, 128] buffers walked in [504, 128] blocks
    over 26 grid steps, and the forwards carry the ``fedzo.forward``
    scope."""
    import re

    from repro import sim
    from repro.kernels import ops
    from repro.workloads import neural

    monkeypatch.setattr(ops, "_auto_interpret",
                        lambda i: False if i is None else i)
    task = neural.make_task("fedavg_cnn", n_train=6000, n_test=1000,
                            n_clients=50, partition="shards",
                            image_shape=(28, 28, 1))
    cfg = neural.default_config(task, n_participating=10, flat_params=True,
                                direction_conv="counter")
    fn = sim.make_experiment_fn(task.loss, cfg, 2)
    params = jax.eval_shape(lambda: task.init(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == 1_663_370
    jaxpr = str(jax.make_jaxpr(fn)(params, None, jax.random.key(0), None,
                                   None, None, task.store))
    grids = re.findall(r"grid=\(([^)]*)\)", jaxpr)
    assert grids and all(g.split(",")[-1].strip() == "26" for g in grids), \
        grids
    assert "Blocked(block_size=504), Blocked(block_size=128)" in jaxpr

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    text = fn.lower(sds(params), None, key, None, None, None,
                    sds(task.store)).compile().as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    names = {re.sub(r"\.\d+$", "", re.search(r"%([\w.\-]+) = ", l)[1])
             for l in calls}
    assert names == {"zo_walk", "zo_dirnorms"}, names
    buffers = {rows for l in calls
               for rows in re.findall(r"f32\[10,(\d+),128\]", l)}
    assert buffers == {str(26 * 504)}, buffers
    assert "fedzo.query/fedzo.forward/" in text
