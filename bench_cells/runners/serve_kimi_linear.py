"""Serving cells of a model with delta-rule linear-attention layers (a matrix
state a slot) beside latent-attention layers whose cache is one absorbed row
a position, and held routed experts beside a shared one
(``models/kimi_linear.py``): ``runners/serve.py``'s closed loop, window,
records, sample and check, over the configuration's own weights
(``weights_kimi_linear``), stage (``make_kimi_linear_stages``) and plain
reference (``reference/kimi_linear.py``).

What depends on the model is here: the set-up (weights, stage, engine,
warm-up of the decode tick and of the ONE chunk shape the mix's prompts are
cut into) and the reference's readings, which walk the model a LAYER at a
time over every sampled sequence (27 layers of float32 weights are 17 GB and
fit no chip; a layer's weights are drawn again from the seed, as
``weights_kimi_linear`` draws them for the program), every sequence padded
to the slot's length so that ``layer`` is compiled once a layer kind.
"""

from __future__ import annotations

import dataclasses
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench_cells import program_spans, weights_kimi_linear
from bench_cells.reference import kimi_linear as reference
from bench_cells.runners import serve
from bench_cells.traffic import generate

from simple_distributed_machine_learning_tpu.models.kimi_linear import (
    KimiLinearConfig,
    make_kimi_linear_stages,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine


def kimi_linear_stage(cfg: KimiLinearConfig, tree: dict):
    """``make_kimi_linear_stages``'s one stage with ``tree`` (the
    benchmark's seeded weights) as its parameters; a tree that does not
    match the shapes the program's builder expects is an error, not a
    silent reshape."""
    held = {}

    def build(key):
        held["stages"] = make_kimi_linear_stages(key, cfg, 1)[0]
        return held["stages"][0].params

    want = jax.eval_shape(build, jax.random.key(0))
    have = jax.eval_shape(lambda t: t, tree)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise SystemExit(
            "bench_cells: the program's Kimi-Linear parameter layout is not "
            "the one bench_cells/weights_kimi_linear.py makes")
    return [dataclasses.replace(held["stages"][0], params=tree)]


def reference_kw(arch: dict) -> dict:
    return dict(n_heads=arch["n_heads"], top_k=arch["top_k"],
                scale=float(arch["route_scale"]),
                first_expert=arch["expert_offset"], eps=arch["rms_eps"])


class _HeldBack:
    """The harness's tracer, started not at the base window's fixed 40 % of
    the window but once ``ready()`` says so. The base loop asks ``start``
    every tick from that mark on until the trace has a directory, and marks
    the tick each time, so the last mark is the tick the trace began at."""

    def __init__(self, tracer, ready):
        self._tracer, self._ready = tracer, ready

    def start(self) -> None:
        if self._ready():
            self._tracer.start()

    def __getattr__(self, name):
        return getattr(self._tracer, name)


class Run(serve.Run):
    def __init__(self, cell, seed: int, spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.mix = cell.traffic
        self.arch = cell.config["kimi_linear_config"]
        # the model's sizes ride the records: the byte counts of the
        # kernels' roofline readers need them
        self.records: dict = {"kimi_linear": self.arch}

    def setup(self) -> dict:
        mix, arch = self.mix, self.arch
        if mix["loop"] != "closed":
            raise SystemExit(f"bench_cells: unknown loop {mix['loop']!r}")
        split = {}
        t = time.perf_counter()
        cfg = KimiLinearConfig(**arch)
        tree = weights_kimi_linear.init_kimi_linear(self.seed, arch)
        jax.block_until_ready(tree)
        split["weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        e = mix["engine"]
        self.eng = InferenceEngine(
            kimi_linear_stage(cfg, tree), cfg, n_slots=e["n_slots"],
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
        """The base window; a traced run's five seconds begin when the first
        request has finished. The shortest answer of this mix is 512 tokens,
        some 20 s of a window that opens on empty slots, and the profiler's
        stop holds the loop for the rest of a window (45 s for 5 s traced),
        so a trace begun at the base runner's 12 s would end the window
        before anything finished, with nothing to compare. Begun later it
        also reads a steady tick (every slot decoding, a chunk now and then)
        and not the opening's chunk in every tick."""
        finished = lambda: any(  # noqa: E731
            len(r["stamps"]) >= r["n_new"] for r in self.sent)
        super().window(seconds, _HeldBack(tracer, finished))
        self._say_what_the_ticks_ran()

    def _say_what_the_ticks_ran(self) -> None:
        """Two lines on stderr (every run, traced or not): how many
        different tokens the sampled requests were served (a random model
        that falls onto one token routes every row alike), and from the
        program's own counters what a decode tick's expert layers hit."""
        served = np.concatenate([t for _, t in self.sample])
        by_tick: dict = {}
        for r in self.sent:
            for tick, tok in zip(r["ticks"], r["handle"].tokens):
                by_tick.setdefault(tick, []).append(tok)
        full = [t for t in by_tick.values()
                if len(t) >= self.mix["engine"]["n_slots"] // 2]
        alike = (statistics.fmean(len(set(t)) / len(t) for t in full)
                 if full else float("nan"))
        print(f"sample: {len(self.sample)} requests, {served.size} served "
              f"tokens, {np.unique(served).size} distinct; of one tick's "
              f"tokens {100 * alike:.1f} % are distinct ({len(full)} ticks)",
              file=sys.stderr, flush=True)
        tracer = program_spans.recorder()
        if tracer is None:
            return
        ticks = [t.attrs for t in program_spans.Window(
            self.records, tracer).ticks if t.attrs.get("experts_hit")]
        if not ticks:
            return
        arch = self.arch
        pairs = (arch["n_layers"] - arch["n_dense"]) * arch["experts_held"]
        mean = lambda k: statistics.fmean(t[k] for t in ticks)  # noqa: E731
        print(f"decode ticks: {len(ticks)}, held experts hit "
              f"{100 * mean('experts_hit') / pairs:.2f} % (least "
              f"{100 * min(t['experts_hit'] for t in ticks) / pairs:.1f}), "
              f"{mean('expert_rows') / mean('experts_hit'):.2f} rows a hit "
              f"expert, most rows on one {mean('expert_rows_max'):.1f}, "
              f"cached positions read {mean('kv_positions'):.0f} over "
              f"{mean('decoding'):.1f} slots, state live in "
              f"{mean('state_slots'):.1f}", file=sys.stderr, flush=True)

    def _rows(self, quant: str | None, seqs, firsts, table, n_out: int):
        """For each sampled sequence, the residual rows ``[n_out, d]`` under
        the positions that chose its served tokens: the ``quant`` forward, a
        layer at a time over all of them."""
        arch = self.arch
        kw = reference_kw(arch)
        hs = [table[jnp.asarray(s)].astype(jnp.float32) for s in seqs]
        for l in range(arch["n_layers"]):
            t = time.perf_counter()
            bp = weights_kimi_linear.init_layer(self.seed, arch, l)
            hs = [reference.layer(bp, h, quant=quant, **kw) for h in hs]
            jax.block_until_ready(hs)
            del bp
            print(f"reference{'' if quant is None else ' ' + quant}: layer "
                  f"{l} over {len(hs)} x {len(hs[0])} positions "
                  f"{time.perf_counter() - t:.1f} s", file=sys.stderr,
                  flush=True)
        return [jax.lax.dynamic_slice_in_dim(h, first, n_out, 0)
                for h, first in zip(hs, firsts)]

    def _readings(self, quant: str | None):
        """Over the sample: each served token's gap under the reference,
        and (control) the gap of the token ``quant`` precision puts first."""
        arch, mix = self.arch, self.mix
        n_out = mix["answer_lengths"]["max"]
        T = mix["engine"]["max_len"]
        seqs, firsts, serveds = [], [], []
        for prompt, toks in self.sample:
            n, first = len(toks), len(prompt) - 1
            if first + n_out > T:
                raise SystemExit("bench_cells: a sampled request does not "
                                 "fit the reference's window")
            seq = np.zeros(T, np.int32)
            seq[:len(prompt)] = prompt
            seq[len(prompt):len(prompt) + n - 1] = toks[:-1]
            served = np.zeros(n_out, np.int32)
            served[:n] = toks
            seqs.append(seq)
            firsts.append(first)
            serveds.append(served)
        served_gaps, control_gaps = [], []
        with jax.default_matmul_precision("highest"):
            ends = weights_kimi_linear.init_ends(self.seed, arch)
            table = ends["embed"]["tok"]
            head = lambda rows, q: reference.head(  # noqa: E731
                ends["head"], rows, eps=arch["rms_eps"], quant=q)
            ref_rows = self._rows(None, seqs, firsts, table, n_out)
            got_rows = ref_rows if quant is None else self._rows(
                quant, seqs, firsts, table, n_out)
            for ref_r, got_r, served, (_, toks) in zip(
                    ref_rows, got_rows, serveds, self.sample):
                ref = head(ref_r, None)
                got = ref if quant is None else head(got_r, quant)
                a, b = serve._gaps(ref, got, jnp.asarray(served))
                served_gaps.append(np.asarray(a, np.float64)[:len(toks)])
                control_gaps.append(np.asarray(b, np.float64)[:len(toks)])
        return np.concatenate(served_gaps), np.concatenate(control_gaps)
