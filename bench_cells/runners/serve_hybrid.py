"""Serving cells of a model with recurrent state (``models/jamba.py``):
``runners/serve.py``'s closed loop, window, records and check, over the
configuration's own weights (``weights_jamba``), stage
(``make_jamba_stages``) and plain reference (``reference/jamba.py``).

Only what depends on the model is here: the set-up (weights, stage, engine,
warm-up) and the reference's readings. The window, the records' keys, the
sample and the comparison are the base runner's, so the generic serve
readers work on these cells unedited.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench_cells import weights_jamba
from bench_cells.reference import jamba as reference
from bench_cells.runners import serve
from bench_cells.traffic import generate

from simple_distributed_machine_learning_tpu.models.jamba import (
    JambaConfig,
    make_jamba_stages,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine


def jamba_stage(cfg: JambaConfig, tree: dict):
    """``make_jamba_stages``'s one stage with ``tree`` (the benchmark's
    seeded weights) as its parameters. The program's builder runs abstractly
    (``jax.eval_shape``); a tree that does not match the shapes it expects
    is an error, not a silent reshape."""
    held = {}

    def build(key):
        held["stages"] = make_jamba_stages(key, cfg, 1)[0]
        return held["stages"][0].params

    want = jax.eval_shape(build, jax.random.key(0))
    have = jax.eval_shape(lambda t: t, tree)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise SystemExit(
            "bench_cells: the program's Jamba parameter layout is not the "
            "one bench_cells/weights_jamba.py makes")
    return [dataclasses.replace(held["stages"][0], params=tree)]


class Run(serve.Run):
    def __init__(self, cell, seed: int, spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.mix = cell.traffic
        self.arch = cell.config["jamba_config"]
        # the model's sizes ride the records: the byte counts of
        # metrics/kernel.selective_scan_roofline_pct.py need them
        self.records: dict = {"jamba": self.arch}

    def setup(self) -> dict:
        mix, arch = self.mix, self.arch
        if mix["loop"] != "closed":
            raise SystemExit(f"bench_cells: unknown loop {mix['loop']!r}")
        split = {}
        t = time.perf_counter()
        cfg = JambaConfig(**arch)
        tree = weights_jamba.init_jamba(self.seed, arch)
        jax.block_until_ready(tree)
        split["weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        e = mix["engine"]
        self.eng = InferenceEngine(
            jamba_stage(cfg, tree), cfg, n_slots=e["n_slots"],
            max_len=e["max_len"], block_size=e["block_size"],
            n_blocks=e["n_blocks"], prefill_chunk=e["prefill_chunk"],
            attn_kernel=e["attn_kernel"],
            cache_dtype=jnp.dtype(e["cache_dtype"]))
        del tree
        self.queues = generate.client_queues(self.seed, mix, arch["vocab"],
                                             mix["rounds"])
        longest = max(len(p) + n for q in self.queues for p, n in q)
        if longest > e["max_len"]:
            raise SystemExit("bench_cells: the mix's longest request does "
                             "not fit the engine's max_len")
        split["engine_build_s"] = time.perf_counter() - t

        # warm exactly the shapes the window uses: the decode tick and one
        # prefill program per chunk length the mix's prompts end in
        t = time.perf_counter()
        chunk = e["prefill_chunk"]
        lengths = {len(p) for q in self.queues for p, _ in q}
        if max(lengths) > chunk:
            raise SystemExit("bench_cells: this runner warms one chunk a "
                             "prompt; the mix has a longer prompt")
        rng = np.random.default_rng(self.seed)
        for n in sorted(lengths):
            self.eng.submit(generate.zipf_tokens(rng, arch["vocab"], n), 3)
        while self.eng.busy:
            self.eng.step()
        split["warm_up_s"] = time.perf_counter() - t
        return split

    def _readings(self, quant: str | None):
        """Over the sample: each served token's gap under the reference,
        and (control) the gap of the token ``quant`` precision puts first."""
        arch, mix = self.arch, self.mix
        n_out = mix["answer_lengths"]["max"]
        T = arch["seq_len"]
        kw = dict(n_heads=arch["n_heads"], n_kv_heads=arch["n_kv_heads"],
                  dt_rank=arch["dt_rank"], eps=arch["rms_eps"], n_out=n_out)
        served_gaps, control_gaps = [], []
        with jax.default_matmul_precision("highest"):
            params = weights_jamba.init_jamba(self.seed, arch)
            for prompt, toks in self.sample:
                n, first = len(toks), len(prompt) - 1
                if first + n_out > T:
                    raise SystemExit("bench_cells: a sampled request does "
                                     "not fit the reference's window")
                seq = np.zeros(T, np.int32)
                seq[:len(prompt)] = prompt
                seq[len(prompt):len(prompt) + n - 1] = toks[:-1]
                served = np.zeros(n_out, np.int32)
                served[:n] = toks
                ref = reference.served_logits(params, jnp.asarray(seq),
                                              first, **kw)
                got = ref if quant is None else reference.served_logits(
                    params, jnp.asarray(seq), first, quant=quant, **kw)
                a, b = serve._gaps(ref, got, jnp.asarray(served))
                served_gaps.append(np.asarray(a, np.float64)[:n])
                control_gaps.append(np.asarray(b, np.float64)[:n])
        return np.concatenate(served_gaps), np.concatenate(control_gaps)
