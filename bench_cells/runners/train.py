"""Training cells: ``make_gpt_stages`` -> ``Pipeline`` ->
``make_train_step`` on the cell's mesh, driven step by step.

Set-up builds ONE compiled step with its state, drives it through the
check's first steps (which are also the warm-up) and hands that same object
to the window. After the window the program's state is freed and the plain
reference follows those first steps from the same seed.
"""

from __future__ import annotations

import gc
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench_cells import check, flops, weights
from bench_cells.reference import gpt2 as reference
from bench_cells.runners.program import gpt_stages
from bench_cells.traffic import generate

from simple_distributed_machine_learning_tpu.models.gpt import GPTConfig
from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
from simple_distributed_machine_learning_tpu.parallel.pipeline import Pipeline
from simple_distributed_machine_learning_tpu.train.optimizer import adamw
from simple_distributed_machine_learning_tpu.train.step import make_train_step


def _packed_leaf_norms(layouts):
    """Jitted: the norm of every leaf of every stage's packed row, as one
    vector (stages in order, leaves in layout order)."""

    @jax.jit
    def norms(buf, other=None):
        rows = buf if other is None else buf - other
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(rows[s, 0, 0, off:off + size])))
            for s, layout in enumerate(layouts)
            for _, off, size in layout])

    return norms


class Run:
    def __init__(self, cell, seed: int, spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.mix = cell.traffic
        self.gpt = cell.config["gpt_config"]
        self.records: dict = {}

    # -- set-up ------------------------------------------------------------

    def setup(self) -> dict:
        mix, gpt = self.mix, self.gpt
        split = {}
        t = time.perf_counter()
        cfg = GPTConfig(**gpt)
        n_stages = mix["mesh"]["n_stages"]
        params = weights.init_gpt(self.seed, gpt)
        # to the host before the pipeline packs them: it packs eagerly on
        # the first device (ravel, concatenate, pad, stack) and then keeps
        # its copy on the host; beside the seeded trees that peaks at 16.78
        # of gpt2-large's 16.91 GB chip, and one run in two fails there
        trees = weights.split_stages(jax.device_get(params), n_stages)
        for leaf in jax.tree.leaves(params):
            leaf.delete()
        self.layouts = [weights.leaf_layout(tr) for tr in trees]
        split["weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        stages, wire_dim, out_shape = gpt_stages(cfg, n_stages, trees)
        mesh = make_mesh(n_stages=n_stages, n_data=mix["mesh"]["n_data"])
        self.pipe = Pipeline(
            stages, mesh, wire_dim, out_shape,
            n_microbatches=mix["n_microbatches"],
            compute_dtype=jnp.dtype(mix["compute_dtype"]),
            schedule=mix["schedule"], remat=mix["remat"])
        del params, trees, stages
        o = mix["optimizer"]
        if o["name"] != "adamw":
            raise SystemExit(f"bench_cells: unknown optimizer {o['name']!r}")
        opt = adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                    weight_decay=o["weight_decay"])
        self.buf = self.pipe.init_params()
        self.state = opt.init(self.buf)
        step = make_train_step(self.pipe, opt)
        split["pipeline_pack_s"] = time.perf_counter() - t

        n_check = mix["check"]["steps"]
        batches = generate.token_batches(
            self.seed, mix, gpt["vocab"], n_check + mix["pool_batches"])
        self.check_batches = batches[:n_check]
        # token ids ride the program's wire as float32 (exact below 2**24)
        self.xs = [jnp.asarray(b[:, :-1], jnp.float32) for b in batches]
        self.ts = [jnp.asarray(b[:, 1:], jnp.int32) for b in batches]
        self.key = jax.random.key(self.seed)

        t = time.perf_counter()
        lowered = step.lower(self.buf, self.state, self.xs[0], self.ts[0],
                             self.key)
        split["trace_lower_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.compiled = lowered.compile()
        split["compile_or_cache_read_s"] = time.perf_counter() - t

        # the first steps: the check's readings, and the warm-up
        t = time.perf_counter()
        norms = _packed_leaf_norms(self.layouts)
        losses = []
        for i in range(n_check):
            losses.append(self._step(i))
            if i == 0:
                # AdamW's first moment after one step is (1 - b1) * g
                grad = norms(self.state[1]) / (1.0 - o["b1"])
        # wait for the steps: the starting point is a new buffer, and a
        # transfer does not queue behind a running step's temporaries
        jax.block_until_ready((self.buf, losses))
        change = norms(self.buf, self.pipe.init_params())
        self.program = {
            "losses": [float(x) for x in jax.device_get(losses)],
            "grad": np.asarray(grad, np.float64).tolist(),
            "change": np.asarray(change, np.float64).tolist()}
        self.n_done = n_check
        split["first_steps_s"] = time.perf_counter() - t
        return split

    def _step(self, i: int):
        """The one call and feed that set-up, the window and the trace all
        drive: batch ``i`` of the check's, then the pool's, in turn."""
        n_check = self.mix["check"]["steps"]
        j = i if i < n_check else n_check + (i - n_check) % (
            len(self.xs) - n_check)
        with self.spans.span("bench.train.step_dispatch"):
            self.buf, self.state, loss = self.compiled(
                self.buf, self.state, self.xs[j], self.ts[j], self.key)
        return loss

    # -- the window --------------------------------------------------------

    def _block(self, x) -> None:
        with self.spans.span("bench.train.block_until_ready"):
            jax.block_until_ready(x)

    def window(self, seconds: float, tracer) -> None:
        mix = self.mix
        losses, pending = [], None
        traced_steps = 0
        t0 = time.perf_counter()
        while True:
            if tracer.enabled and len(losses) == 2 and tracer.dir is None:
                # a traced stretch of whole steps, device drained at both ends
                self._block((self.buf, pending))
                tracer.start()
                for _ in range(mix["trace_steps"]):
                    losses.append(self._step(self.n_done + len(losses)))
                    if pending is not None:
                        self._block(pending)
                    pending = losses[-1]
                self._block((self.buf, pending))
                tracer.stop()
                traced_steps = mix["trace_steps"]
            losses.append(self._step(self.n_done + len(losses)))
            if pending is not None:
                self._block(pending)     # at most one step ahead
            pending = losses[-1]
            if time.perf_counter() - t0 >= seconds:
                break
        self._block((losses[-1], self.buf))
        t1 = time.perf_counter()
        values = np.asarray(jax.device_get(losses), np.float64)
        print(f"window: {len(losses)} steps in {t1 - t0:.3f} s, last loss "
              f"{values[-1]:.4f}", file=sys.stderr, flush=True)
        tokens_per_step = mix["batch"] * mix["seq_len"]
        self.records = {
            "kind": "train",
            "window_s": t1 - t0,
            "steps": len(losses),
            "tokens": len(losses) * tokens_per_step,
            "tokens_per_step": tokens_per_step,
            "flops_per_token": flops.train_flops_per_token(
                self.gpt, mix["seq_len"]),
            "traced_steps": traced_steps,
            "n_stages": mix["mesh"]["n_stages"],
            "n_microbatches": mix["n_microbatches"],
            "attempted": len(losses),
            "failed": int((~np.isfinite(values)).sum()),
            "last_loss": float(values[-1]),
        }

    # -- the check ---------------------------------------------------------

    def free(self) -> None:
        del self.buf, self.state, self.compiled, self.pipe, self.xs, self.ts
        gc.collect()

    def reference_readings(self, quant=None, compute=None) -> dict:
        """The plain reference over the check's steps, from the seed; with
        ``quant`` the control: the same in a lower precision."""
        mix, gpt = self.mix, self.gpt
        batches = [(jnp.asarray(b[:, :-1], jnp.int32),
                    jnp.asarray(b[:, 1:], jnp.int32))
                   for b in self.check_batches]
        n_stages = mix["mesh"]["n_stages"]
        devices = jax.devices()[:self.cell.chips]
        mesh = (jax.sharding.Mesh(np.array(devices), ("rows",))
                if len(devices) > 1 else None)
        losses, grad, change = reference.train_steps(
            lambda: weights.init_gpt(self.seed, gpt), batches,
            n_heads=gpt["n_heads"], optimizer=mix["optimizer"],
            rows=mix["check"]["reference_rows"], quant=quant,
            compute=None if compute is None else jnp.dtype(compute),
            mesh=mesh)

        def by_stage(tree):
            return [float(x) for st in weights.split_stages(tree, n_stages)
                    for x in jax.tree.leaves(st)]

        return {"losses": losses, "grad": by_stage(grad),
                "change": by_stage(change)}

    def values(self, got: dict, ref: dict) -> dict:
        """The numbers compared: the first step's loss, the first gradient's
        norm (mean over the leaves of the gap) and the norm of the
        parameters' change (the worst leaf's gap). The later losses and the
        other readings of the gaps are printed, not compared (PERF.md,
        section 2, says why)."""
        names = [f"stage{s}{path}" for s, layout in enumerate(self.layouts)
                 for path, _, _ in layout]
        grad = check.leaf_gaps(got["grad"], ref["grad"])
        change = check.leaf_gaps(got["change"], ref["change"])
        worst = lambda g: names[max(range(len(g)), key=g.__getitem__)]  # noqa: E731
        self.records["check_detail"] = {
            "losses": got["losses"], "reference_losses": ref["losses"],
            "grad_gap_max": max(grad), "grad_worst_leaf": worst(grad),
            "change_gap_mean": statistics.fmean(change),
            "change_worst_leaf": worst(change)}
        return {"loss_step1": abs(got["losses"][0] - ref["losses"][0]),
                "grad_norm": statistics.fmean(grad),
                "update_norm": max(change)}

    def check(self) -> dict:
        self.free()
        t = time.perf_counter()
        self.ref = self.reference_readings()
        self.records["reference_s"] = time.perf_counter() - t
        return self.values(self.program, self.ref)

    def control(self) -> dict:
        """The control's numbers: the reference in the precision below the
        configuration's, put in the program's place. After :meth:`check`."""
        return self.values(self.reference_readings(
            **self.cell.config["control"]["train"]), self.ref)
