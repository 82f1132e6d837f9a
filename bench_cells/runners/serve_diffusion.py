"""Serving cells of a model that generates by diffusion over blocks
(``models/sdar.py``): ``runners/serve.py``'s closed loop, window and
records, over the configuration's own weights (``weights_sdar``), stage
(``make_sdar_stages``) and plain reference (``reference/sdar.py``).

Only what depends on the model is here: the set-up (weights, stage, engine,
warm-up) and the check. The served path keeps every committed block as it
was served (its tokens and the forward that fixed each:
``Request.blocks``), which rebuilds the input of every denoising forward, so
the reference follows what was served in ONE two-stream forward a request
and a near-tie cannot send the two apart. Two readings, each the mean over
every position the sample's requests fixed:

- ``gap_mean``: how far the served token's logit lies below the
  reference's best, at the position and forward where it was fixed;
- ``pick_gap_mean``: how far the log-probability the reference gives its
  best token at the position that was fixed lies below the same at the
  best of the positions still masked at that forward (where a forward
  fixes ``n``: the ``n`` fixed against the ``n`` best, rank by rank).

The control reads both for what an int8-operand forward would have fixed.
"""

from __future__ import annotations

import dataclasses
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench_cells import program_spans, weights_sdar
from bench_cells.reference import sdar as reference
from bench_cells.runners import serve
from bench_cells.traffic import generate

from simple_distributed_machine_learning_tpu.models.sdar import (
    SdarConfig,
    make_sdar_stages,
)
from simple_distributed_machine_learning_tpu.serve import InferenceEngine


def sdar_stage(cfg: SdarConfig, tree: dict):
    """``make_sdar_stages``'s one stage with ``tree`` (the benchmark's
    seeded weights) as its parameters; a tree that does not match the
    shapes the program's builder expects is an error."""
    held = {}

    def build(key):
        held["stages"] = make_sdar_stages(key, cfg, 1)[0]
        return held["stages"][0].params

    want = jax.eval_shape(build, jax.random.key(0))
    have = jax.eval_shape(lambda t: t, tree)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise SystemExit(
            "bench_cells: the program's SDAR parameter layout is not the "
            "one bench_cells/weights_sdar.py makes")
    return [dataclasses.replace(held["stages"][0], params=tree)]


def two_streams(prompt, blocks, block: int, mask_id: int):
    """The reference's inputs for one served request: the clean sequence
    (the prompt, then every committed block's tokens from where the prompt
    ends), and one noisy group for every denoising forward of every block,
    block by block and forward by forward: ``(clean [L], noisy [R, B],
    starts [R])``. ``blocks``: ``Request.blocks``, ``(start, tokens,
    order)`` each."""
    clean = [int(t) for t in prompt]
    noisy, starts = [], []
    for start, toks, order in blocks:
        clean[start:] = clean[start:len(prompt)] + [
            int(t) for t in toks[max(len(prompt) - start, 0):]]
        for j in range(1, max(order) + 1):
            noisy.append([int(t) if o < j else mask_id
                          for t, o in zip(toks, order)])
            starts.append(start)
    return (np.asarray(clean, np.int32),
            np.asarray(noisy, np.int32).reshape(-1, block),
            np.asarray(starts, np.int32))


@jax.jit
def _row_stats(logits, served):
    """Per row of ``logits [N, V]``: the best logit, the log-probability of
    the best token, the logit of the ``served`` token, the best token."""
    best = logits.max(-1)
    pick = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return (best, best - jax.nn.logsumexp(logits, axis=-1), pick,
            jnp.argmax(logits, -1).astype(jnp.int32))


def step_gaps(blocks, ref_stats, got_gap, got_conf, block: int):
    """Over the noisy groups (in :func:`two_streams`' order) of one
    request, ``(gap, pick_gap)`` rows: for every position that was fixed,
    of what was served; and for every forward, of what the control's
    forward would have fixed (the position its own confidence ``got_conf``
    puts first among the masked, and there the token its own logits put
    first, whose gap under the reference is ``got_gap``). ``ref_stats``:
    :func:`_row_stats` of the reference's ``[R * block]`` rows against the
    served tokens; the reference's numbers decide every gap."""
    best, conf, served_logit = (np.asarray(a, np.float64).reshape(-1, block)
                                for a in ref_stats[:3])
    got_gap = np.asarray(got_gap, np.float64).reshape(-1, block)
    got_conf = np.asarray(got_conf, np.float64).reshape(-1, block)
    served, control = [], []
    r = 0
    for _start, _toks, order in blocks:
        order = np.asarray(order)
        for j in range(1, int(order.max()) + 1):
            masked = order >= j
            # the forward fixed n positions: the reference's n most
            # confident of the masked ones, against those, rank by rank
            tops = np.sort(conf[r][masked])[::-1]
            top = tops[0]
            fixed = np.flatnonzero(order == j)
            fixed = fixed[np.argsort(-conf[r][fixed], kind="stable")]
            for rank, o in enumerate(fixed):
                served.append((best[r, o] - served_logit[r, o],
                               tops[rank] - conf[r, o]))
            o = int(np.flatnonzero(masked)[np.argmax(got_conf[r][masked])])
            control.append((got_gap[r, o], top - conf[r, o]))
            r += 1
    return (np.asarray(served).reshape(-1, 2),
            np.asarray(control).reshape(-1, 2))


class Run(serve.Run):
    def __init__(self, cell, seed: int, spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.mix = cell.traffic
        self.arch = cell.config["sdar_config"]
        # the model's sizes ride the records: the byte counts of
        # metrics/kernel.moe_experts_roofline_pct.py need them
        self.records: dict = {"sdar": self.arch}

    def _weights(self):
        return weights_sdar.init_sdar(self.seed, self.arch)

    def setup(self) -> dict:
        mix, arch = self.mix, self.arch
        if mix["loop"] != "closed":
            raise SystemExit(f"bench_cells: unknown loop {mix['loop']!r}")
        d = mix["diffusion"]
        if (d["remasking"] != "low_confidence_static" or any(
                d[k] != arch[k] for k in ("block_length", "denoising_steps"))):
            raise SystemExit(f"bench_cells: the mix's diffusion {d} is not "
                             f"the configuration's schedule")
        split = {}
        t = time.perf_counter()
        cfg = SdarConfig(**arch)
        tree = self._weights()
        jax.block_until_ready(tree)
        split["weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        e = mix["engine"]
        self.eng = InferenceEngine(
            sdar_stage(cfg, tree), cfg, n_slots=e["n_slots"],
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

        # warm exactly the shapes the window uses: the block tick and one
        # prefill program per chunk length the mix's prompts are cut into
        t = time.perf_counter()
        chunk = e["prefill_chunk"]
        lengths = {min(chunk, n - p0) for q in self.queues for p, _ in q
                   for n in [len(p) - len(p) % arch["block_length"]]
                   for p0 in range(0, n, chunk)}
        # a stream of its own: the queues' first prompts are drawn from the
        # seed's, and a warm-up prompt that opens like one of them would
        # leave it a shared prefix and a chunk length nothing has warmed
        rng = np.random.default_rng([self.seed, 1])
        for n in sorted(lengths):
            self.eng.submit(generate.zipf_tokens(rng, arch["vocab"], n),
                            arch["block_length"] + 1)
        while self.eng.busy:
            self.eng.step()
        split["warm_up_s"] = time.perf_counter() - t
        return split

    def window(self, seconds: float, tracer) -> None:
        super().window(seconds, tracer)
        self._say_what_the_ticks_ran()
        # the check follows the blocks as they were served, not the
        # tokens: the base runner's sample keeps each request's own prompt
        # array, by which its handle is found again
        handle = {id(r["prompt"]): r["handle"] for r in self.sent}
        self.sample = [(prompt, handle[id(prompt)].blocks)
                       for prompt, _ in self.sample]

    def _say_what_the_ticks_ran(self) -> None:
        """One line on stderr from the program's own counters (every run,
        traced or not): a forward's cost follows the experts it hit, which
        follows the seed's weights and tokens."""
        tracer = program_spans.recorder()
        if tracer is None:
            return
        ticks = [t.attrs for t in program_spans.Window(
            self.records, tracer).ticks if t.attrs.get("forwards")]
        if not ticks:
            return
        pairs = self.arch["n_layers"] * self.arch["n_experts"]
        mean = lambda k: statistics.fmean(t[k] for t in ticks)  # noqa: E731
        print(f"block ticks: {len(ticks)} decoded, {mean('forwards'):.2f} "
              f"forwards and {mean('commits'):.2f} commits a tick, experts "
              f"hit {100 * mean('experts_hit') / pairs:.2f} % (least "
              f"{100 * min(t['experts_hit'] for t in ticks) / pairs:.1f}), "
              f"most rows on one expert {mean('expert_rows_max'):.0f}",
              file=sys.stderr, flush=True)

    def _readings(self, quant: str | None):
        arch = self.arch
        blk = arch["block_length"]
        kw = dict(n_heads=arch["n_heads"], n_kv_heads=arch["n_kv_heads"],
                  top_k=arch["top_k"], block=blk,
                  theta=float(arch["rope_theta"]), eps=arch["rms_eps"])
        # one shape for the whole sample: the clean stream padded to the
        # slot's budget, the noisy groups to the most a request has
        T = arch["seq_len"]
        most = max(sum(max(o) for _, _, o in blocks)
                   for _, blocks in self.sample)

        def pad(a, m):
            return jnp.asarray(np.concatenate(
                [a, np.zeros((m - len(a), *a.shape[1:]), a.dtype)]))

        served, control = [], []
        with jax.default_matmul_precision("highest"):
            params = self._weights()
            for prompt, blocks in self.sample:
                clean, noisy, starts = two_streams(
                    prompt, blocks, blk, arch["mask_id"])
                args = (pad(clean, T), pad(noisy, most), pad(starts, most))
                # a row's served token: what its position ended as
                final = pad(np.concatenate([
                    np.tile(np.asarray(t, np.int32), (max(o), 1))
                    for _, t, o in blocks]), most).reshape(-1)
                ref = reference.noisy_logits(params, *args, **kw)
                ref_stats = _row_stats(ref, final)
                got_gap, got_conf = ref_stats[0] - ref_stats[2], ref_stats[1]
                if quant is not None:
                    _, got_conf, _, first = _row_stats(
                        reference.noisy_logits(params, *args, quant=quant,
                                               **kw), final)
                    best, _, at_first, _ = _row_stats(ref, first)
                    got_gap = best - at_first
                n = len(starts) * blk
                a, b = step_gaps(
                    blocks, [np.asarray(x)[:n] for x in ref_stats],
                    np.asarray(got_gap)[:n], np.asarray(got_conf)[:n], blk)
                served.append(a)
                control.append(b)
        return np.concatenate(served), np.concatenate(control)

    @staticmethod
    def _values(gaps: np.ndarray) -> dict:
        return {"gap_mean": float(gaps[:, 0].mean()),
                "pick_gap_mean": float(gaps[:, 1].mean())}

    def check(self) -> dict:
        self.free()
        t = time.perf_counter()
        served, _ = self._readings(None)
        self.records["reference_s"] = time.perf_counter() - t
        self.records["check_detail"] = {
            "requests": len(self.sample), "positions": int(len(served)),
            "gap_max": float(served[:, 0].max()),
            "pick_gap_max": float(served[:, 1].max())}
        return self._values(served)

    def control(self) -> dict:
        _, ctrl = self._readings(
            self.cell.config["control"]["serve"]["quant"])
        self.records["check_detail"] = {
            "gap_max": float(ctrl[:, 0].max()),
            "pick_gap_max": float(ctrl[:, 1].max())}
        return self._values(ctrl)
