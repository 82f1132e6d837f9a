"""One-off block-size tuning sweep for the Pallas flash kernel on chip.

Times fwd+bwd at several (block_q, block_k) against XLA dense, bf16,
dh in {64, 128}, T in {2048, 4096, 8192}. Prints one JSON line per point.
"""

import functools
import json
import time

import jax
import jax.numpy as jnp


def _time(fn, *args, reps=10):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        float(jax.tree.leaves(out)[0].ravel()[0])
        best = min(best, (time.perf_counter() - t0) / reps)
    return best * 1e3


def main():
    import sys, os
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from simple_distributed_machine_learning_tpu.ops.attention import (
        causal_attention_core,
    )
    from simple_distributed_machine_learning_tpu.ops.flash_attention import (
        flash_attention,
    )

    B, H = 4, 8
    for dh in (64, 128):
        for t in (2048, 4096, 8192):
            key = jax.random.key(0)
            kq, kk, kv = jax.random.split(key, 3)
            shape = (B, H, t, dh)
            q = jax.random.normal(kq, shape).astype(jnp.bfloat16)
            k = jax.random.normal(kk, shape).astype(jnp.bfloat16)
            v = jax.random.normal(kv, shape).astype(jnp.bfloat16)

            def fwd_bwd(attn, q, k, v):
                def loss(q, k, v):
                    return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)
                return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

            dense_oom = False
            try:
                dense_ms = _time(jax.jit(functools.partial(
                    fwd_bwd, causal_attention_core)), q, k, v)
            except Exception as e:
                # only a memory failure is the flash kernel's win; anything
                # else (compile/lowering error) must not masquerade as one
                dense_ms = None
                dense_oom = ("RESOURCE_EXHAUSTED" in str(e)
                             or "memory" in str(e).lower())
                print(json.dumps({"t": t, "dh": dh,
                                  "dense": f"FAIL {str(e)[:120]}",
                                  "dense_oom": dense_oom}))
            # trimmed grid: every point costs a fwd+bwd XLA compile.
            # (128,128) is the default baseline; larger bq cuts K/V passes,
            # larger bk cuts grid steps.
            for bq, bk in ((128, 128), (256, 256), (256, 512),
                           (512, 256), (512, 512), (512, 1024)):
                if bq > t or bk > t:
                    continue
                attn = functools.partial(flash_attention,
                                         block_q=bq, block_k=bk)
                try:
                    ms = _time(jax.jit(functools.partial(fwd_bwd, attn)),
                               q, k, v)
                    print(json.dumps({
                        "t": t, "dh": dh, "bq": bq, "bk": bk,
                        "flash_ms": round(ms, 3),
                        "dense_ms": (round(dense_ms, 3)
                                     if dense_ms is not None else None),
                        "dense_oom": dense_oom,
                        "speedup": (round(dense_ms / ms, 2)
                                    if dense_ms is not None else None)}))
                except Exception as e:
                    print(json.dumps({"t": t, "dh": dh, "bq": bq,
                                      "bk": bk,
                                      "err": str(e)[:120]}))
                sys.stdout.flush()


if __name__ == "__main__":
    main()
