"""Serving cells of a model whose layers are one part each, in a published
order (``models/nemotron_h.py``): ``runners/serve.py``'s closed loop, window,
records, sample and check, over the configuration's own weights
(``weights_nemotron_h``), stage (``make_nemotron_h_stages``) and plain
reference (``reference/nemotron_h.py``).

Only what depends on the model is here: the set-up (weights, stage, engine,
warm-up of the decode tick and of the ONE chunk shape the mix's prompts are
cut into) and the reference's readings.
"""

from __future__ import annotations

import dataclasses
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench_cells import program_spans, weights_nemotron_h
from bench_cells.reference import nemotron_h as reference
from bench_cells.runners import serve
from bench_cells.traffic import generate

from simple_distributed_machine_learning_tpu.models.nemotron_h import (
    NemotronHConfig,
    make_nemotron_h_stages,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine


def nemotron_h_stage(cfg: NemotronHConfig, tree: dict):
    """``make_nemotron_h_stages``'s one stage with ``tree`` (the benchmark's
    seeded weights) as its parameters; a tree that does not match the shapes
    the program's builder expects is an error, not a silent reshape."""
    held = {}

    def build(key):
        held["stages"] = make_nemotron_h_stages(key, cfg, 1)[0]
        return held["stages"][0].params

    want = jax.eval_shape(build, jax.random.key(0))
    have = jax.eval_shape(lambda t: t, tree)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise SystemExit(
            "bench_cells: the program's Nemotron-H parameter layout is not "
            "the one bench_cells/weights_nemotron_h.py makes")
    return [dataclasses.replace(held["stages"][0], params=tree)]


def reference_kw(arch: dict) -> dict:
    return dict(n_heads=arch["n_heads"], n_kv_heads=arch["n_kv_heads"],
                n_groups=arch["n_groups"], top_k=arch["top_k"],
                scale=float(arch["route_scale"]),
                first_expert=arch["expert_offset"], eps=arch["rms_eps"])


class Run(serve.Run):
    def __init__(self, cell, seed: int, spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.mix = cell.traffic
        self.arch = cell.config["nemotron_h_config"]
        # the model's sizes ride the records: the byte counts of the
        # kernels' roofline readers need them
        self.records: dict = {"nemotron_h": self.arch}

    def setup(self) -> dict:
        mix, arch = self.mix, self.arch
        if mix["loop"] != "closed":
            raise SystemExit(f"bench_cells: unknown loop {mix['loop']!r}")
        split = {}
        t = time.perf_counter()
        cfg = NemotronHConfig(**arch)
        tree = weights_nemotron_h.init_nemotron_h(self.seed, arch)
        jax.block_until_ready(tree)
        split["weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        e = mix["engine"]
        self.eng = InferenceEngine(
            nemotron_h_stage(cfg, tree), cfg, n_slots=e["n_slots"],
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

        # warm exactly the shapes the window uses: the decode tick and the
        # one chunk length every prompt of the mix is cut into
        t = time.perf_counter()
        chunk = e["prefill_chunk"]
        if any(len(p) % chunk for q in self.queues for p, _ in q):
            raise SystemExit("bench_cells: this runner warms one chunk "
                             "shape; the mix has a prompt that is no whole "
                             "number of chunks")
        rng = np.random.default_rng([self.seed, 1])
        self.eng.submit(generate.zipf_tokens(rng, arch["vocab"], 2 * chunk),
                        3)
        while self.eng.busy:
            self.eng.step()
        split["warm_up_s"] = time.perf_counter() - t
        return split

    def window(self, seconds: float, tracer) -> None:
        super().window(seconds, tracer)
        self._say_what_the_ticks_ran()

    def _say_what_the_ticks_ran(self) -> None:
        """One line on stderr from the program's own counters (every run,
        traced or not): a tick's cost follows the held experts it hit."""
        tracer = program_spans.recorder()
        if tracer is None:
            return
        ticks = [t.attrs for t in program_spans.Window(
            self.records, tracer).ticks if t.attrs.get("experts_hit")]
        if not ticks:
            return
        pairs = self.arch["pattern"].count("E") * self.arch["experts_held"]
        mean = lambda k: statistics.fmean(t[k] for t in ticks)  # noqa: E731
        print(f"decode ticks: {len(ticks)}, held experts hit "
              f"{100 * mean('experts_hit') / pairs:.2f} % (least "
              f"{100 * min(t['experts_hit'] for t in ticks) / pairs:.1f}), "
              f"{mean('expert_rows') / mean('experts_hit'):.2f} rows a hit "
              f"expert, most rows on one {mean('expert_rows_max'):.0f}",
              file=sys.stderr, flush=True)

    def _readings(self, quant: str | None):
        """Over the sample: each served token's gap under the reference,
        and (control) the gap of the token ``quant`` precision puts first."""
        arch, mix = self.arch, self.mix
        n_out = mix["answer_lengths"]["max"]
        T = arch["seq_len"]
        kw = dict(reference_kw(arch), n_out=n_out)
        served_gaps, control_gaps = [], []
        with jax.default_matmul_precision("highest"):
            params = weights_nemotron_h.init_nemotron_h(self.seed, arch)
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
