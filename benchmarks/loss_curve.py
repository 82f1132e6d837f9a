"""The north-star loss-curve comparison (BASELINE.json: "match the
CPU-backend loss curve").

Runs the REFERENCE semantics — LeNet, MNIST/10 (6000 train / 1000 test),
batch 60, SGD(lr=0.1, momentum=0.5), 10 epochs, fixed batch order
(``/root/reference/simple_distributed.py:86-136``) — twice from the SAME
torch-default initial weights:

- torch: the reference's model/loop math, single process (the RPC split
  does not change the numerics — tests/test_multiprocess.py covers the
  process topology separately);
- ours: the 2-stage pipeline engine on a (stage=2) mesh, packed buffer,
  ppermute hops.

Dropout is OFF on both sides (SURVEY §6 parity caveat: train-time dropout
is stochastic and framework RNGs differ by construction; the reference
additionally has the worker-eval-dropout bug SURVEY §3.5 tells us not to
carry over).

Prints one JSON line per epoch per side and writes
benchmarks/loss_curves.json.

Run (CPU is fine; this is a numerics check, not a perf check):
    python benchmarks/loss_curve.py
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

# a numerics run on two virtual CPU devices: it must not take the TPU chip
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 2)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "loss_curves.json")

EPOCHS = 10
BATCH = 60
LR, MOMENTUM = 0.1, 0.5

# NOTE on the reference hyperparameters: lr=0.1/momentum=0.5 is tuned for
# real MNIST (which needs network access this environment doesn't have). On
# the synthetic fallback task BOTH frameworks learn for ~1.5 epochs and then
# collapse to the uniform predictor IN LOCKSTEP (identical 2.3026 plateaus,
# max rel diff <3%) — trajectory parity through a divergence is still
# parity, but a second run at --lr 0.01 records a healthy learning curve.


def _data():
    from simple_distributed_machine_learning_tpu.data.mnist import load_mnist
    return load_mnist(os.path.join(REPO, "data"))   # synthetic fallback ok


def run_torch(train_ds, test_ds, lr=LR) -> dict:
    import torch
    import torch.nn.functional as F

    from test_torch_parity import _torch_forward, _torch_lenet

    m = _torch_lenet()
    params = [p for mod in m.values() for p in mod.parameters()]
    opt = torch.optim.SGD(params, lr=lr, momentum=MOMENTUM)

    def to_torch(x):        # NHWC -> NCHW
        return torch.from_numpy(np.ascontiguousarray(
            x.transpose(0, 3, 1, 2)))

    epochs = []
    n_train = len(train_ds.x)
    for epoch in range(1, EPOCHS + 1):
        tot, nb = 0.0, 0
        for s in range(0, n_train, BATCH):
            x = to_torch(train_ds.x[s:s + BATCH])
            y = torch.from_numpy(train_ds.y[s:s + BATCH].astype(np.int64))
            opt.zero_grad()
            loss = F.nll_loss(_torch_forward(m, x), y)
            loss.backward()
            opt.step()
            tot += float(loss)
            nb += 1
        with torch.no_grad():
            logp = _torch_forward(m, to_torch(test_ds.x))
            y = torch.from_numpy(test_ds.y.astype(np.int64))
            test_loss = float(F.nll_loss(logp, y, reduction="sum")) / len(y)
            acc = int((logp.argmax(1) == y).sum())
        row = {"side": "torch", "epoch": epoch,
               "train_loss": round(tot / nb, 6),
               "test_loss": round(test_loss, 6),
               "test_acc": acc, "n_test": len(y)}
        epochs.append(row)
        print(json.dumps(row))
    return {"epochs": epochs}


def run_ours(train_ds, test_ds, lr=LR) -> dict:
    import jax

    from test_torch_parity import _export_torch_params, _torch_lenet

    from simple_distributed_machine_learning_tpu.models.lenet import (
        FEATURES,
        IN_SHAPE,
        N_CLASSES,
        _conv_apply,
        _fc_apply,
    )
    from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
    from simple_distributed_machine_learning_tpu.parallel.pipeline import (
        Pipeline,
        Stage,
    )
    from simple_distributed_machine_learning_tpu.train.optimizer import sgd
    from simple_distributed_machine_learning_tpu.train.step import (
        make_eval_step,
    )

    conv_params, fc_params = _export_torch_params(_torch_lenet())
    stages = [
        Stage(apply=_conv_apply, params=conv_params, in_shape=IN_SHAPE),
        Stage(apply=_fc_apply, params=fc_params, in_shape=(FEATURES,)),
    ]
    n_dev = len(jax.devices())
    n_stages = 2 if n_dev >= 2 else 1
    if n_stages == 1:       # single device: fuse the two stages
        def fused(params, x, key, deterministic):
            h = _conv_apply(params["conv"], x, key, deterministic)
            return _fc_apply(params["fc"], h, key, deterministic)
        stages = [Stage(apply=fused,
                        params={"conv": conv_params, "fc": fc_params},
                        in_shape=IN_SHAPE)]
    mesh = make_mesh(n_stages=n_stages, n_data=1)
    pipe = Pipeline(stages, mesh, 28 * 28, N_CLASSES)
    opt = sgd(lr, MOMENTUM)
    buf = pipe.init_params()
    state = opt.init(buf)

    @jax.jit
    def step(buf, state, x, t):
        def loss_fn(b):
            # deterministic=True: dropout off, matching the torch side
            return pipe.loss_and_logits(b, x, t, jax.random.key(0),
                                        deterministic=True)[0]
        loss, grads = jax.value_and_grad(loss_fn)(buf)
        buf, state = opt.update(grads, state, buf)
        return buf, state, loss

    eval_step = make_eval_step(pipe)
    epochs = []
    n_train = len(train_ds.x)
    for epoch in range(1, EPOCHS + 1):
        tot, nb = 0.0, 0
        for s in range(0, n_train, BATCH):
            x = train_ds.x[s:s + BATCH]
            y = train_ds.y[s:s + BATCH].astype(np.int32)
            buf, state, loss = step(buf, state, x, y)
            tot += float(loss)
            nb += 1
        sum_nll, correct = 0.0, 0
        n_test = len(test_ds.x)
        for s in range(0, n_test, BATCH):
            x = test_ds.x[s:s + BATCH]
            y = test_ds.y[s:s + BATCH].astype(np.int32)
            sl, c = eval_step(buf, x, y, jax.random.key(0),
                              np.int32(len(x)))
            sum_nll += float(sl)
            correct += int(c)
        row = {"side": "ours", "epoch": epoch,
               "train_loss": round(tot / nb, 6),
               "test_loss": round(sum_nll / n_test, 6),
               "test_acc": correct, "n_test": n_test}
        epochs.append(row)
        print(json.dumps(row))
    return {"epochs": epochs}


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--lr", type=float, default=LR)
    ap.add_argument("--out", type=str, default=OUT)
    args = ap.parse_args()
    train_ds, test_ds = _data()
    ours = run_ours(train_ds, test_ds, lr=args.lr)
    torch_res = run_torch(train_ds, test_ds, lr=args.lr)
    rows = {"config": {"epochs": EPOCHS, "batch": BATCH, "lr": args.lr,
                       "momentum": MOMENTUM, "n_train": len(train_ds.x),
                       "n_test": len(test_ds.x), "dropout": "off (SURVEY §6)"},
            "ours": ours["epochs"], "torch": torch_res["epochs"]}
    # the comparison the files exist for: per-epoch curve agreement
    max_rel = max(
        abs(a["train_loss"] - b["train_loss"])
        / max(abs(b["train_loss"]), 1e-9)
        for a, b in zip(rows["ours"], rows["torch"]))
    rows["max_train_loss_rel_diff"] = round(max_rel, 6)
    rows["final_acc_ours"] = rows["ours"][-1]["test_acc"]
    rows["final_acc_torch"] = rows["torch"][-1]["test_acc"]
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=2)
    print(json.dumps({"max_train_loss_rel_diff": rows["max_train_loss_rel_diff"],
                      "final_acc_ours": rows["final_acc_ours"],
                      "final_acc_torch": rows["final_acc_torch"]}))


if __name__ == "__main__":
    main()
