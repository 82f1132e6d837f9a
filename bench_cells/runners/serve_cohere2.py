"""Serving cells of a model with window and full attention layers in one
pool, a parallel block and held routed experts beside shared ones
(``models/cohere2.py``): ``runners/serve.py``'s closed loop, window, records
and check, over the configuration's own weights (``weights_cohere2``), stage
(``make_cohere2_stages``) and plain reference (``reference/cohere2.py``).

What depends on the model or on the mix is here: the set-up (weights, stage,
engine with its two block counts, warm-up of the decode tick and of the ONE
chunk shape the mix's prompts are cut into); the queues of a mix with
several CLASSES of prompt lengths in one queue (``traffic/generate.py``'s
stratified sizes, a class at a time, dealt out in one fixed order); the
sample (the longest finished request and a few of each class); and the
reference's readings, which walk the model a LAYER at a time over every
sampled sequence (32,768 positions of float32 activations beside all the
weights do not fit a chip; a layer's weights are drawn again from the seed,
as ``weights_cohere2`` draws them for the program).
"""

from __future__ import annotations

import dataclasses
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench_cells import program_spans, weights_cohere2
from bench_cells.reference import cohere2 as reference
from bench_cells.runners import serve, serve_zaya
from bench_cells.traffic import generate

from simple_distributed_machine_learning_tpu.models.cohere2 import (
    Cohere2Config,
    make_cohere2_stages,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine

#: the lengths the reference is compiled for: a sampled sequence is padded
#: to the next of them (causal attention makes the padding irrelevant)
_SHORT_T = 4096
_LONG_STEP = 8192


def cohere2_stage(cfg: Cohere2Config, tree: dict):
    """``make_cohere2_stages``'s one stage with ``tree`` (the benchmark's
    seeded weights) as its parameters; a tree that does not match the shapes
    the program's builder expects is an error, not a silent reshape."""
    held = {}

    def build(key):
        held["stages"] = make_cohere2_stages(key, cfg, 1)[0]
        return held["stages"][0].params

    want = jax.eval_shape(build, jax.random.key(0))
    have = jax.eval_shape(lambda t: t, tree)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise SystemExit(
            "bench_cells: the program's Cohere2 parameter layout is not the "
            "one bench_cells/weights_cohere2.py makes")
    return [dataclasses.replace(held["stages"][0], params=tree)]


def reference_kw(arch: dict) -> dict:
    return dict(n_heads=arch["n_heads"], n_kv_heads=arch["n_kv_heads"],
                theta=float(arch["rope_theta"]), top_k=arch["top_k"],
                first_expert=arch["expert_offset"],
                n_shared=arch["n_shared"], eps=arch["ln_eps"])


def class_sizes(mix: dict) -> list[tuple[int, int]]:
    """The mix's fixed set of ``(prompt length, answer length)`` pairs, one
    round: each class's share of ``round_size``, its prompt lengths and the
    mix's answer lengths stratified by ``generate.request_sizes`` (a
    pairing of its own a class)."""
    sizes = []
    for i, cls in enumerate(mix["classes"]):
        n = round(cls["share"] * mix["round_size"])
        sizes += generate.request_sizes(dict(
            mix, round_size=n, prompt_lengths=cls["prompt_lengths"],
            pairing_seed=mix["pairing_seed"] + 2 + i))
    if len(sizes) != mix["round_size"]:
        raise SystemExit("bench_cells: the classes' shares do not add up to "
                         "round_size")
    return sizes


def client_queues(seed: int, mix: dict, vocab: int, rounds: int):
    """``generate.client_queues`` over :func:`class_sizes`: every round deals
    the classes' sizes out over the clients in one order that the mix fixes
    (another one each round), so short and long requests stand in one queue;
    the seed draws the tokens."""
    rng = np.random.default_rng(seed)
    order_rng = np.random.default_rng(mix["pairing_seed"] + 1)
    sizes = class_sizes(mix)
    n_clients = mix["clients"]
    if len(sizes) % n_clients:
        raise SystemExit("bench_cells: round_size must be a multiple of "
                         "clients")
    queues: list[list] = [[] for _ in range(n_clients)]
    exponent = mix["tokens"].get("exponent", 1.0)
    for _ in range(rounds):
        for j, idx in enumerate(order_rng.permutation(len(sizes))):
            plen, alen = sizes[int(idx)]
            queues[j % n_clients].append(
                (generate.zipf_tokens(rng, vocab, plen, exponent), alen))
    return queues


def _class_of(mix: dict, prompt_len: int) -> int:
    for i, cls in enumerate(mix["classes"]):
        pl = cls["prompt_lengths"]
        if pl["min"] <= prompt_len <= pl["max"]:
            return i
    raise SystemExit(f"bench_cells: a prompt of {prompt_len} is of no class")


def _padded(n: int) -> int:
    return _SHORT_T if n <= _SHORT_T else -(-n // _LONG_STEP) * _LONG_STEP


class Run(serve.Run):
    def __init__(self, cell, seed: int, spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.mix = cell.traffic
        self.arch = cell.config["cohere2_config"]
        # the model's sizes ride the records: the byte counts of the
        # kernels' roofline readers need them
        self.records: dict = {"cohere2": self.arch}

    def setup(self) -> dict:
        mix, arch = self.mix, self.arch
        if mix["loop"] != "closed":
            raise SystemExit(f"bench_cells: unknown loop {mix['loop']!r}")
        split = {}
        t = time.perf_counter()
        cfg = Cohere2Config(**arch)
        tree = weights_cohere2.init_cohere2(self.seed, arch)
        jax.block_until_ready(tree)
        split["weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        e = mix["engine"]
        self.eng = InferenceEngine(
            cohere2_stage(cfg, tree), cfg, n_slots=e["n_slots"],
            max_len=e["max_len"], block_size=e["block_size"],
            n_blocks=e["n_blocks"], n_window_blocks=e["n_window_blocks"],
            prefill_chunk=e["prefill_chunk"], attn_kernel=e["attn_kernel"],
            cache_dtype=jnp.dtype(e["cache_dtype"]))
        del tree
        self.queues = client_queues(self.seed, mix, arch["vocab"],
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
        self._pick_sample()
        self._say_what_the_ticks_ran()

    def _pick_sample(self) -> None:
        """The sample the reference will follow: the longest finished
        request, ``check.of_each_class`` of every class the seed draws from
        the finished ones (as far as a class finished that many) and, up to
        ``check.requests``, more drawn from the rest."""
        mix = self.mix
        done = [r for r in self.sent if len(r["stamps"]) >= r["n_new"]]
        rng = np.random.default_rng(self.seed)
        order = sorted(range(len(done)), key=lambda i: -(
            len(done[i]["prompt"]) + done[i]["n_new"]))
        pick = order[:1]
        rest = [int(i) for i in rng.permutation(order[1:])]
        for c in range(len(mix["classes"])):
            have = sum(_class_of(mix, len(done[i]["prompt"])) == c
                       for i in pick)
            for i in rest:
                if have >= mix["check"]["of_each_class"]:
                    break
                if i not in pick and _class_of(
                        mix, len(done[i]["prompt"])) == c:
                    pick.append(i)
                    have += 1
        pick += [i for i in rest if i not in pick][
            :max(0, mix["check"]["requests"] - len(pick))]
        self.sample = [(done[i]["prompt"],
                        np.asarray(done[i]["handle"].tokens, np.int32))
                       for i in pick]

    def _say_what_the_ticks_ran(self) -> None:
        """One line on stderr from the program's own counters (every run,
        traced or not): a decode tick's cost follows the held experts its
        rows hit and the cached positions its two layer kinds read."""
        tracer = program_spans.recorder()
        if tracer is None:
            return
        window = program_spans.Window(self.records, tracer)
        self._say_which_ticks_stalled(window)
        ticks = [t.attrs for t in window.ticks if t.attrs.get("experts_hit")]
        if not ticks:
            return
        pairs = self.arch["n_layers"] * self.arch["experts_held"]
        mean = lambda k: statistics.fmean(t[k] for t in ticks)  # noqa: E731
        print(f"decode ticks: {len(ticks)}, held experts hit "
              f"{100 * mean('experts_hit') / pairs:.2f} % (least "
              f"{100 * min(t['experts_hit'] for t in ticks) / pairs:.1f}), "
              f"{mean('expert_rows') / mean('experts_hit'):.2f} rows a hit "
              f"expert, most rows on one {mean('expert_rows_max'):.1f}, "
              f"cached positions read {mean('kv_positions'):.0f} (a window "
              f"layer {mean('kv_window_positions'):.0f}) over "
              f"{mean('decoding'):.1f} slots, blocks held a full layer "
              f"{mean('kv_full_blocks'):.0f}, a window layer "
              f"{mean('kv_window_blocks'):.0f}",
              file=sys.stderr, flush=True)

    # one stall record a tick over four times the median, untraced runs too
    _say_which_ticks_stalled = serve_zaya.Run._say_which_ticks_stalled

    def _rows(self, quant: str | None, seqs, firsts, table, n_out: int):
        """For each sampled sequence, the residual rows ``[n_out, d]`` under
        the positions that chose its served tokens: the ``quant`` forward, a
        layer at a time over all of them."""
        arch = self.arch
        kw = reference_kw(arch)
        hs = [table[jnp.asarray(s)].astype(jnp.float32) for s in seqs]
        for l in range(arch["n_layers"]):
            t = time.perf_counter()
            bp = weights_cohere2.init_layer(self.seed, arch, l)
            window = reference.layer_window(l, arch["window"],
                                            arch["full_every"])
            hs = [reference.layer(bp, h, window=window, quant=quant, **kw)
                  for h in hs]
            jax.block_until_ready(hs)
            del bp
            print(f"reference{'' if quant is None else ' ' + quant}: layer "
                  f"{l} over {[len(h) for h in hs]} positions "
                  f"{time.perf_counter() - t:.1f} s", file=sys.stderr,
                  flush=True)
        return [jax.lax.dynamic_slice_in_dim(h, first, n_out, 0)
                for h, first in zip(hs, firsts)]

    def _readings(self, quant: str | None):
        """Over the sample: each served token's gap under the reference,
        and (control) the gap of the token ``quant`` precision puts first."""
        arch, mix = self.arch, self.mix
        n_out = mix["answer_lengths"]["max"]
        seqs, firsts, serveds = [], [], []
        for prompt, toks in self.sample:
            n, first = len(toks), len(prompt) - 1
            T = _padded(first + n_out)
            if T > arch["seq_len"]:
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
            ends = weights_cohere2.init_ends(self.seed, arch)
            table, norm_f = ends["embed"]["tok"], ends["head"]["norm_f"]
            head = lambda rows, q: reference.head(  # noqa: E731
                norm_f, table, rows, eps=arch["ln_eps"],
                logit_scale=arch["logit_scale"], quant=q)
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
