"""Why did the gpt_bf16 bench row halve under AdamW? Compiled cost analysis.

Round-5 sweep: gpt_bf16 fell 3 838 -> 1 853 samples/sec (same flops/sample)
when the row's optimizer switched sgd(0.1, m=0.5) -> adamw(1e-3) for a
finite loss. AdamW's arithmetic is a handful of fused elementwise passes
(~0.5 ms of HBM traffic on this 12.6M-param model), nowhere near the
observed +4.4 ms/step — so compare the COMPILED programs, not the math:
XLA's cost analysis (flops / bytes accessed) and memory analysis for the
same scanned train step under each optimizer.

Runs entirely on CPU (compile-only, nothing executed): the suspicion is a
structural effect (scan-carry copies of the m/v state, remat interaction),
which shows up in bytes-accessed ratios on any backend.

Prints one JSON line per optimizer and a verdict line.
"""

import json
import os
import sys

# a compile-only script: held to the CPU so it never takes the TPU chip
# from a process that is measuring on it
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

assert jax.default_backend() == "cpu", jax.default_backend()


def main() -> None:
    from simple_distributed_machine_learning_tpu.models.gpt import (
        GPTConfig,
        make_gpt_stages,
    )
    from simple_distributed_machine_learning_tpu.parallel.mesh import make_mesh
    from simple_distributed_machine_learning_tpu.parallel.pipeline import (
        Pipeline,
    )
    from simple_distributed_machine_learning_tpu.train.optimizer import (
        adamw,
        sgd,
    )
    from simple_distributed_machine_learning_tpu.train.step import (
        make_scanned_train_step,
    )

    # the bench's gpt_bf16 spec (bench.py::_configs), smaller pool to keep
    # CPU compile time sane; per-step structure is what matters
    cfg = GPTConfig(vocab=1024, seq_len=128, d_model=256, n_heads=4,
                    n_layers=4)
    batch, pool, steps = 4, 2, 8
    stages, wire_dim, out_dim = make_gpt_stages(jax.random.key(0), cfg,
                                                n_stages=1)
    mesh = make_mesh(n_stages=1, n_data=1)
    pipe = Pipeline(stages, mesh, wire_dim, out_dim, n_microbatches=1,
                    compute_dtype=jnp.bfloat16)
    buf = pipe.init_params()
    xs = jnp.zeros((pool, batch, cfg.seq_len), jnp.float32)
    ts = jnp.zeros((pool, batch, cfg.seq_len), jnp.int32)
    key = jax.random.key(0)

    from simple_distributed_machine_learning_tpu.train.optimizer import (
        Optimizer,
    )

    def adamw_folded(lr, b1=0.9, b2=0.999, eps=1e-8, wd=0.01) -> Optimizer:
        """torch-identical AdamW with bias correction folded into scalars:
        update = lr*sqrt(bc2)/bc1 * m / (sqrt(v) + eps*sqrt(bc2)), which is
        algebraically torch's lr/bc1 * m / (sqrt(v)/sqrt(bc2) + eps) — but
        avoids materializing m/bc1 and v/bc2 as full tensors."""

        def init(params):
            zeros = lambda: jax.tree.map(jnp.zeros_like, params)
            return (jnp.zeros((), jnp.int32), zeros(), zeros())

        def update(grads, state, params):
            step, m, v = state
            step = step + 1
            t = step.astype(jnp.float32)
            m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
            v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v,
                             grads)
            rbc2 = jnp.sqrt(1 - b2 ** t)
            alpha = lr * rbc2 / (1 - b1 ** t)

            def upd(p, m_, v_):
                return p * (1 - lr * wd) - alpha * m_ / (
                    jnp.sqrt(v_) + eps * rbc2)

            return jax.tree.map(upd, params, m, v), (step, m, v)

        return Optimizer(init, update)

    def adamw_bf16state(lr) -> Optimizer:
        """AdamW with m/v stored in bf16 (halved state traffic; the update
        math still runs in f32 via upcast)."""
        inner = adamw_folded(lr)

        def init(params):
            step, m, v = inner.init(params)
            tobf = lambda t_: jax.tree.map(
                lambda x: x.astype(jnp.bfloat16), t_)
            return (step, tobf(m), tobf(v))

        def update(grads, state, params):
            step, m, v = state
            tof32 = lambda t_: jax.tree.map(
                lambda x: x.astype(jnp.float32), t_)
            new_params, (step, m, v) = inner.update(
                grads, (step, tof32(m), tof32(v)), params)
            tobf = lambda t_: jax.tree.map(
                lambda x: x.astype(jnp.bfloat16), t_)
            return new_params, (step, tobf(m), tobf(v))

        return Optimizer(init, update)

    def two_buffer_sgd(lr) -> Optimizer:
        """Isolation probe: TWO momentum-like state buffers, no counter, no
        scalar chain — pure extra-state cost."""

        def init(params):
            zeros = lambda: jax.tree.map(jnp.zeros_like, params)
            return (zeros(), zeros())

        def update(grads, state, params):
            m, v = state
            m = jax.tree.map(lambda m_, g: 0.9 * m_ + g, m, grads)
            v = jax.tree.map(lambda v_, g: 0.5 * v_ + g, v, grads)
            new_params = jax.tree.map(
                lambda p, m_, v_: p - lr * (m_ + v_), params, m, v)
            return new_params, (m, v)

        return Optimizer(init, update)

    def adamw_nobias(lr, eps=1e-8) -> Optimizer:
        """Isolation probe: m/v + sqrt update WITHOUT the step counter /
        bias-correction scalar chain."""

        def init(params):
            zeros = lambda: jax.tree.map(jnp.zeros_like, params)
            return (zeros(), zeros())

        def update(grads, state, params):
            m, v = state
            m = jax.tree.map(lambda m_, g: 0.9 * m_ + 0.1 * g, m, grads)
            v = jax.tree.map(lambda v_, g: 0.999 * v_ + 0.001 * g * g, v,
                             grads)
            new_params = jax.tree.map(
                lambda p, m_, v_: p - lr * m_ / (jnp.sqrt(v_) + eps),
                params, m, v)
            return new_params, (m, v)

        return Optimizer(init, update)

    def adamw_running(lr, b1=0.9, b2=0.999, eps=1e-8, wd=0.01) -> Optimizer:
        """torch-identical AdamW with the bias-correction powers carried as
        RUNNING PRODUCTS (b1pow *= b1 per step) instead of ``b1 ** t`` on a
        traced exponent — the pow-of-traced-scalar is the suspected
        fusion-breaker."""

        def init(params):
            zeros = lambda: jax.tree.map(jnp.zeros_like, params)
            return (jnp.ones((), jnp.float32), jnp.ones((), jnp.float32),
                    zeros(), zeros())

        def update(grads, state, params):
            b1pow, b2pow, m, v = state
            b1pow = b1pow * b1
            b2pow = b2pow * b2
            m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
            v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v,
                             grads)
            rbc2 = jnp.sqrt(1 - b2pow)
            alpha = lr * rbc2 / (1 - b1pow)

            def upd(p, m_, v_):
                return p * (1 - lr * wd) - alpha * m_ / (
                    jnp.sqrt(v_) + eps * rbc2)

            return jax.tree.map(upd, params, m, v), (b1pow, b2pow, m, v)

        return Optimizer(init, update)

    def sgd_counted(lr, momentum=0.5) -> Optimizer:
        """Isolation probe: sgd(momentum) plus an unused 0-d step counter in
        the state — does a bare scalar in the scan carry trigger the
        blowup?"""

        def init(params):
            return (jnp.zeros((), jnp.int32),
                    jax.tree.map(jnp.zeros_like, params))

        def update(grads, state, params):
            count, buf = state
            buf = jax.tree.map(lambda b, g: momentum * b + g, buf, grads)
            new_params = jax.tree.map(lambda p, b: p - lr * b, params, buf)
            return new_params, (count + 1, buf)

        return Optimizer(init, update)

    def sgd_counted_used(lr, momentum=0.5) -> Optimizer:
        """Isolation probe: like sgd_counted but the update MULTIPLIES by a
        counter-derived traced scalar (constant-1 by construction) — does a
        scalar-dependent elementwise kernel trigger the blowup?"""

        def init(params):
            return (jnp.zeros((), jnp.int32),
                    jax.tree.map(jnp.zeros_like, params))

        def update(grads, state, params):
            count, buf = state
            count = count + 1
            scale = jnp.where(count > 0, 1.0, 0.5)   # traced, always 1.0
            buf = jax.tree.map(lambda b, g: momentum * b + g, buf, grads)
            new_params = jax.tree.map(lambda p, b: p - (lr * scale) * b,
                                      params, buf)
            return new_params, (count, buf)

        return Optimizer(init, update)

    def adamw_nobias_wd(lr, eps=1e-8, wd=0.01) -> Optimizer:
        """Isolation probe: adamw_nobias + decoupled weight decay with
        CONSTANT multiplier."""
        inner = adamw_nobias(lr, eps=eps)

        def update(grads, state, params):
            params = jax.tree.map(lambda p: p * (1 - lr * wd), params)
            return inner.update(grads, state, params)

        return Optimizer(inner.init, update)

    def adamw_eps_traced(lr, eps=1e-8) -> Optimizer:
        """Isolation probe: adamw_nobias but the denominator eps is a
        TRACED scalar carried in the state (constant-valued)."""

        def init(params):
            zeros = lambda: jax.tree.map(jnp.zeros_like, params)
            return (jnp.float32(eps), zeros(), zeros())

        def update(grads, state, params):
            eps_t, m, v = state
            m = jax.tree.map(lambda m_, g: 0.9 * m_ + 0.1 * g, m, grads)
            v = jax.tree.map(lambda v_, g: 0.999 * v_ + 0.001 * g * g, v,
                             grads)
            new_params = jax.tree.map(
                lambda p, m_, v_: p - lr * m_ / (jnp.sqrt(v_) + eps_t),
                params, m, v)
            return new_params, (eps_t, m, v)

        return Optimizer(init, update)

    def adamw_mulform(lr, b1=0.9, b2=0.999, eps=1e-8, wd=0.01) -> Optimizer:
        """Candidate fix: torch-identical AdamW where every traced
        bias-correction enters as a MULTIPLY and eps stays a CONSTANT add —
        update = p*(1-lr*wd) - (lr/bc1)*m / (sqrt(v*(1/bc2)) + eps), which
        is exactly torch's m_hat / (sqrt(v_hat) + eps) form."""

        def init(params):
            zeros = lambda: jax.tree.map(jnp.zeros_like, params)
            return (jnp.zeros((), jnp.int32), zeros(), zeros())

        def update(grads, state, params):
            step, m, v = state
            step = step + 1
            t = step.astype(jnp.float32)
            m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
            v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v,
                             grads)
            scaled_lr = lr / (1 - b1 ** t)        # scalar ops only
            inv_bc2 = 1.0 / (1 - b2 ** t)

            def upd(p, m_, v_):
                denom = jnp.sqrt(v_ * inv_bc2) + eps
                return p * (1 - lr * wd) - (scaled_lr * m_) / denom

            return jax.tree.map(upd, params, m, v), (step, m, v)

        return Optimizer(init, update)

    rows = {}
    # Default: just the sgd-vs-adamw fast-path comparison that regression-
    # guards the gate fix. The update-formula rewrites (nobias/eps_traced/
    # mulform/folded/...) were diagnostic probes for the round-5 packed-path
    # investigation; it concluded the blowup tracked the state-shape gate,
    # not the arithmetic, so they are retired to OPT_COST_FULL.
    variants = (("sgd", sgd(0.1, momentum=0.5)),
                ("adamw", adamw(1e-3)))
    if os.environ.get("OPT_COST_FULL"):
        variants = variants + (
            ("adamw_nobias", adamw_nobias(1e-3)),
            ("adamw_nobias_wd", adamw_nobias_wd(1e-3)),
            ("adamw_eps_traced", adamw_eps_traced(1e-3)),
            ("adamw_mulform", adamw_mulform(1e-3)),
            ("two_buffer_sgd", two_buffer_sgd(0.1)),
            ("adamw_running", adamw_running(1e-3)),
            ("sgd_counted", sgd_counted(0.1)),
            ("sgd_counted_used", sgd_counted_used(0.1)),
            ("adamw_folded", adamw_folded(1e-3)),
            ("adamw_bf16state", adamw_bf16state(1e-3)))

    hlo_dir = os.environ.get("OPT_COST_HLO_DIR")
    for name, opt in variants:
        opt_state = opt.init(buf)
        step = make_scanned_train_step(pipe, opt, pool_steps=steps)
        lowered = step.lower(buf, opt_state, xs, ts, key)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):          # older jax returns [dict]
            cost = cost[0]
        mem = compiled.memory_analysis()
        row = {
            "optimizer": name,
            "flops_per_window": cost.get("flops"),
            "bytes_accessed_per_window": cost.get("bytes accessed"),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
        }
        rows[name] = row
        print(json.dumps(row))
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            with open(os.path.join(hlo_dir, f"{name}.hlo.txt"), "w") as f:
                f.write(compiled.as_text())

    ref = rows.get("sgd") or rows.get("adamw_nobias")
    a = rows.get("adamw")
    if ref and a:
        verdict = {
            "reference": ref["optimizer"],
            "flops_ratio_adamw_over_ref":
                round(a["flops_per_window"] / ref["flops_per_window"], 3)
                if ref.get("flops_per_window") else None,
            "bytes_ratio_adamw_over_ref":
                round(a["bytes_accessed_per_window"]
                      / ref["bytes_accessed_per_window"], 3)
                if ref.get("bytes_accessed_per_window") else None,
            "temp_ratio_adamw_over_ref":
                round(a["temp_bytes"] / ref["temp_bytes"], 3)
                if ref.get("temp_bytes") else None,
        }
        print(json.dumps({"verdict": verdict}))


if __name__ == "__main__":
    main()
