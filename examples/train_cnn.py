"""Train a CNN with FedZO in one jit (the Sec. V-B neural track,
DESIGN.md §11).

    PYTHONPATH=src python examples/train_cnn.py [--smoke] [--task cnn]

A trainable LeNet-style SmallCNN (or, with ``--task fedavg_cnn``, FedAvg's
published MNIST CNN at its track's own lr and μ, on the flat-kernel plan) on
Dirichlet-label-skewed synthetic image shards: the whole multi-round
federation — participation draws, minibatch sampling, the H·b2
forward-only ZO queries per client, size-weighted aggregation, and the
in-scan top-1 test-accuracy eval — runs as ONE compiled program.
``--task softmax`` / ``--task transformer`` swap the model through the
same bridge; no gradient of the model is ever taken.
"""
import argparse

from repro import sim
from repro.workloads import neural

ap = argparse.ArgumentParser()
ap.add_argument("--smoke", action="store_true", help="CI-sized run")
ap.add_argument("--task", default="cnn",
                choices=("softmax", "cnn", "fedavg_cnn", "transformer"))
ap.add_argument("--rounds", type=int, default=0)
args = ap.parse_args()

if args.task == "fedavg_cnn":
    # the track's own lr (the ones below diverge on this model), on the
    # flat-kernel plan that its d = 1.66M wants
    over = {"flat_params": True, "direction_conv": "counter"}
elif args.smoke and args.task == "cnn":
    over = {"lr": 2e-2}
else:
    over = {"lr": 5e-2}
if args.smoke:
    task = neural.make_task(args.task, n_train=400, n_test=96, n_clients=6,
                            n_classes=4, **{
                                "cnn": {"image_shape": (12, 12, 1),
                                        "width": 4},
                                "fedavg_cnn": {"image_shape": (8, 8, 1)},
                            }.get(args.task, {"n_features": 32}))
    cfg = neural.default_config(task, local_iters=4, b1=16, b2=16, **over)
    rounds = args.rounds or 10
else:
    task = neural.make_task(args.task, n_train=2000, n_test=512,
                            n_clients=10)
    cfg = neural.default_config(task, **over)
    rounds = args.rounds or 30

# the true untrained baseline — the engine's in-scan eval at round 0 runs
# after the first round's update, so history()[0] already reflects training
acc0 = float(task.accuracy(neural.params_init(task, cfg.seed), task.test))
res = neural.run(task, cfg, rounds, eval_every=2)
evals = [row for row in sim.history(res) if "test_acc" in row]
for row in evals:
    print({k: round(v, 4) if isinstance(v, float) else v
           for k, v in row.items()})
print(f"final test accuracy: {evals[-1]['test_acc']:.3f} "
      f"(untrained: {acc0:.3f})")
