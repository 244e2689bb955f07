"""Shared by the CPU tests: a cell shrunk to a size the CPU holds, run
through the harness with the chip check skipped.

    JAX_PLATFORMS=cpu python bench/tests/tiny.py <workload> [<fault>]

prints the result line of ``run.run_cell``. ``<fault>`` plants a fault in
the program under the harness (see ``FAULTS``)."""
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

# every cell small enough to run in seconds with the Pallas kernels
# interpreted (a clients-mesh cell on four virtual devices)
TINY = dict(n_clients=8, n_participating=4, local_iters=2, b2=3, b1=5,
            n_train=800, n_test=100, eval_rows=64, segment_rounds=2)


def overrides(workload):
    return dict(TINY)


def _wrap_rounds(wrap):
    """Put ``wrap(round_fn)`` in place of the engine's round: the plain
    cohort round and the clients-mesh round alike."""
    from repro import sim
    from repro.core import fedzo
    from repro.sim import shard

    fedzo.round_simulated = wrap(fedzo.round_simulated)
    orig = shard.make_sharded_round

    def make_sharded_round(*a, **kw):
        return wrap(orig(*a, **kw))

    shard.make_sharded_round = sim.make_sharded_round = make_sharded_round


def _unchanged():
    """The round returns the server state it was given."""
    def wrap(round_fn):
        def run(loss_fn, server_params, *a, **kw):
            out = round_fn(loss_fn, server_params, *a, **kw)
            return (server_params,) + tuple(out[1:])
        return run

    _wrap_rounds(wrap)


def _half_batch():
    """Every client's minibatch loses its second half; the loss is the mean
    over the rest."""
    import jax

    def wrap(round_fn):
        def run(loss_fn, server_params, client_batches, *a, **kw):
            half = jax.tree.map(lambda x: x[:, :, :x.shape[2] // 2],
                                client_batches)
            return round_fn(loss_fn, server_params, half, *a, **kw)
        return run

    _wrap_rounds(wrap)


def _no_exchange():
    """The clients mesh never sums its partial means across chips."""
    from repro.sim import shard

    class _Lax:
        def __getattr__(self, name):
            import jax
            if name == "psum":
                return lambda x, axis: x
            return getattr(jax.lax, name)

    class _Jax:
        lax = _Lax()

        def __getattr__(self, name):
            import jax
            return getattr(jax, name)

    shard.jax = _Jax()


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "no_exchange": _no_exchange}


def main():
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    import run

    workload = sys.argv[1]
    if len(sys.argv) > 2:
        FAULTS[sys.argv[2]]()
    result = run.run_cell(workload, 2 ** 31 + 9, 0.5, 0, require_chip=False,
                          overrides=overrides(workload))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
