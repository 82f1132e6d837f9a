"""Serving cells of a model whose every layer is compressed convolutional
attention and a top-1 mixture (``models/zaya.py``): ``runners/serve.py``'s
closed loop, window, records, sample and check, over the configuration's own
weights (``weights_zaya``), stage (``make_zaya_stages``) and plain reference
(``reference/zaya.py``).

Only what depends on the model is here: the set-up (weights, stage, engine,
warm-up of the decode tick and of the ONE chunk shape the mix's prompts are
cut into) and the reference's readings, which take the head a slice of the
vocabulary at a time (2,048 rows of 262,272 float32 logits are 2.1 GB, twice
over beside the control's).
"""

from __future__ import annotations

import dataclasses
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench_cells import program_runs, program_spans, weights_zaya
from bench_cells.reference import zaya as reference
from bench_cells.runners import serve
from bench_cells.traffic import generate

from simple_distributed_machine_learning_tpu.models.zaya import (
    ZayaConfig,
    make_zaya_stages,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine


def zaya_stage(cfg: ZayaConfig, tree: dict):
    """``make_zaya_stages``'s one stage with ``tree`` (the benchmark's
    seeded weights) as its parameters; a tree that does not match the shapes
    the program's builder expects is an error, not a silent reshape."""
    held = {}

    def build(key):
        held["stages"] = make_zaya_stages(key, cfg, 1)[0]
        return held["stages"][0].params

    want = jax.eval_shape(build, jax.random.key(0))
    have = jax.eval_shape(lambda t: t, tree)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise SystemExit(
            "bench_cells: the program's ZAYA parameter layout is not the "
            "one bench_cells/weights_zaya.py makes")
    return [dataclasses.replace(held["stages"][0], params=tree)]


def reference_kw(arch: dict) -> dict:
    return dict(n_heads=arch["n_heads"], n_kv_heads=arch["n_kv_heads"],
                theta=float(arch["rope_theta"]),
                rotated=int(arch["rotary_fraction"] * arch["head_dim"]),
                eps=arch["rms_eps"])


class Run(serve.Run):
    def __init__(self, cell, seed: int, spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.mix = cell.traffic
        self.arch = cell.config["zaya_config"]
        # the model's sizes ride the records: the byte counts of the
        # kernels' roofline readers need them
        self.records: dict = {"zaya": self.arch}

    def setup(self) -> dict:
        mix, arch = self.mix, self.arch
        if mix["loop"] != "closed":
            raise SystemExit(f"bench_cells: unknown loop {mix['loop']!r}")
        split = {}
        t = time.perf_counter()
        cfg = ZayaConfig(**arch)
        tree = weights_zaya.init_zaya(self.seed, arch)
        jax.block_until_ready(tree)
        split["weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        e = mix["engine"]
        self.eng = InferenceEngine(
            zaya_stage(cfg, tree), cfg, n_slots=e["n_slots"],
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
        traced or not): a decode tick's cost follows the experts its rows
        hit and the cached positions it read."""
        tracer = program_spans.recorder()
        if tracer is None:
            return
        window = program_spans.Window(self.records, tracer)
        self._say_which_ticks_stalled(window)
        ticks = [t.attrs for t in window.ticks if t.attrs.get("experts_hit")]
        if not ticks:
            return
        pairs = self.arch["n_layers"] * self.arch["n_experts"]
        mean = lambda k: statistics.fmean(t[k] for t in ticks)  # noqa: E731
        print(f"decode ticks: {len(ticks)}, experts hit "
              f"{100 * mean('experts_hit') / pairs:.2f} % (least "
              f"{100 * min(t['experts_hit'] for t in ticks) / pairs:.1f}), "
              f"most rows on one {mean('expert_rows_max'):.1f}, cached "
              f"positions read {mean('kv_positions'):.0f} over "
              f"{mean('decoding'):.1f} slots",
              file=sys.stderr, flush=True)

    def _say_which_ticks_stalled(self, window) -> None:
        """One stall record a tick over four times the median (the eight
        longest at most), untraced runs too: a pause of the runtime costs
        this cell 0.4 % of its window, and the record says where the host
        stood in it."""
        lengths = sorted(program_spans.seconds(t) for t in window.ticks)
        stalled = sorted((t for t in window.ticks if program_spans.seconds(t)
                          > 4 * lengths[len(lengths) // 2]),
                         key=program_spans.seconds, reverse=True)
        for t in stalled[:8]:
            print(f"{t.start_ns * 1e-9 - self.records['t0']:.3f} s into the "
                  f"window (at {t.start_ns * 1e-9:.3f} on the host's clock), "
                  + program_runs.stall_record(window, None, t),
                  file=sys.stderr, flush=True)

    def _readings(self, quant: str | None):
        """Over the sample: each served token's gap under the reference,
        and (control) the gap of the token ``quant`` precision puts first."""
        arch, mix = self.arch, self.mix
        n_out = mix["answer_lengths"]["max"]
        T = arch["seq_len"]
        kw = reference_kw(arch)
        served_gaps, control_gaps = [], []
        with jax.default_matmul_precision("highest"):
            params = weights_zaya.init_zaya(self.seed, arch)
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
                a, b = reference.served_gaps(
                    params, jnp.asarray(seq), first, jnp.asarray(served),
                    quant=quant, **kw)
                served_gaps.append(np.asarray(a, np.float64)[:n])
                control_gaps.append(np.asarray(b, np.float64)[:n])
        return np.concatenate(served_gaps), np.concatenate(control_gaps)
