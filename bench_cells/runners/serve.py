"""Serving cells: ``InferenceEngine`` (paged pool, chunked prefill) under a
closed loop of clients, each sending its next request the moment the last
one completed. The harness stamps every token itself through ``submit``'s
``on_token`` callback.

After the window the engine is freed and the plain reference runs once over
a seeded sample of the finished requests (the longest among them): each
served token's logit against the reference's best at that position.
"""

from __future__ import annotations

import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench_cells import weights
from bench_cells.reference import gpt2 as reference
from bench_cells.runners.program import gpt_stages
from bench_cells.traffic import generate

from simple_distributed_machine_learning_tpu.models.gpt import GPTConfig
from simple_distributed_machine_learning_tpu.serve import InferenceEngine


@jax.jit
def _gaps(ref_logits, got_logits, served):
    """Per position: how far the served token's reference logit lies below
    the reference's best, and the same for the token ``got_logits`` puts
    first (the control's reading)."""
    best = ref_logits.max(-1)
    pick = lambda ids: jnp.take_along_axis(  # noqa: E731
        ref_logits, ids[:, None], axis=-1)[:, 0]
    return best - pick(served), best - pick(jnp.argmax(got_logits, -1))


class Run:
    def __init__(self, cell, seed: int, spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.mix = cell.traffic
        self.gpt = cell.config["gpt_config"]
        self.records: dict = {}

    # -- set-up ------------------------------------------------------------

    def setup(self) -> dict:
        mix, gpt = self.mix, self.gpt
        if mix["loop"] != "closed":
            raise SystemExit(f"bench_cells: unknown loop {mix['loop']!r}")
        split = {}
        t = time.perf_counter()
        cfg = GPTConfig(**gpt)
        tree = weights.init_gpt(self.seed, gpt)
        jax.block_until_ready(tree)
        split["weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        e = mix["engine"]
        stages, _, _ = gpt_stages(cfg, 1, [tree])
        self.eng = InferenceEngine(
            stages, cfg, params=[tree], n_slots=e["n_slots"],
            max_len=e["max_len"], block_size=e["block_size"],
            n_blocks=e["n_blocks"], prefill_chunk=e["prefill_chunk"],
            attn_kernel=e["attn_kernel"],
            cache_dtype=jnp.dtype(e["cache_dtype"]))
        del tree, stages
        self.queues = generate.client_queues(self.seed, mix, gpt["vocab"],
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
        warm = sorted({chunk + (n % chunk) if n > chunk and n % chunk
                       else min(n, chunk) for n in lengths})
        rng = np.random.default_rng(self.seed)
        for n in warm:
            self.eng.submit(generate.zipf_tokens(rng, gpt["vocab"], n), 3)
        while self.eng.busy:
            self.eng.step()
        split["warm_up_s"] = time.perf_counter() - t
        return split

    # -- the window --------------------------------------------------------

    def _submit(self, c: int, now: float) -> None:
        if not self.queues[c]:
            raise SystemExit("bench_cells: the mix ran out of requests; "
                             "raise its rounds")
        prompt, n_new = self.queues[c].pop(0)
        rec = {"client": c, "prompt": prompt, "n_new": n_new,
               "t_submit": now, "stamps": [], "ticks": []}

        def on_token(_request, _token, rec=rec):
            rec["stamps"].append(time.perf_counter())
            rec["ticks"].append(self.tick)

        with self.spans.span("bench.serve.submit"):
            rec["handle"] = self.eng.submit(
                prompt, n_new,
                temperature=self.mix["sampling"]["temperature"],
                on_token=on_token)
        self.current[c] = rec
        self.sent.append(rec)

    def window(self, seconds: float, tracer) -> None:
        mix = self.mix
        self.tick, self.sent = 0, []
        self.current: dict = {}
        ticks = []
        trace_at = 0.4 * seconds
        t0 = time.perf_counter()
        for c in range(mix["clients"]):
            self._submit(c, time.perf_counter())
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            if tracer.enabled and tracer.dir is None and now - t0 >= trace_at:
                tracer.start()
                self.records["traced_ticks"] = [self.tick, None]
            if tracer.running and (time.perf_counter() - tracer.started_at
                                   >= mix["trace_seconds"]):
                tracer.stop()
                self.records["traced_ticks"][1] = self.tick
            ts = time.perf_counter()
            with self.spans.span("bench.serve.engine_step"):
                emitted = self.eng.step()
            te = time.perf_counter()
            ticks.append((ts, te, emitted))
            self.tick += 1
            with self.spans.span("bench.serve.clients"):
                for c, rec in list(self.current.items()):
                    if len(rec["stamps"]) >= rec["n_new"]:
                        self._submit(c, time.perf_counter())
        t1 = time.perf_counter()
        if tracer.running:
            tracer.stop()
            self.records["traced_ticks"][1] = self.tick
        done = [r for r in self.sent if len(r["stamps"]) >= r["n_new"]]
        shed = [r for r in self.sent
                if r["handle"].state not in ("queued", "active", "done")]
        self.records.update({
            "kind": "serve", "t0": t0, "window_s": t1 - t0,
            "ticks": ticks, "n_slots": mix["engine"]["n_slots"],
            "requests": [{"prompt_len": len(r["prompt"]),
                          "n_new": r["n_new"], "t_submit": r["t_submit"],
                          "stamps": r["stamps"], "ticks": r["ticks"]}
                         for r in self.sent],
            "attempted": len(done) + len(shed), "failed": len(shed),
            "cache_itemsize": jnp.dtype(
                mix["engine"]["cache_dtype"]).itemsize,
        })
        durations = sorted(te - ts for ts, te, _ in ticks)
        print(f"window: {len(ticks)} ticks (median "
              f"{durations[len(durations) // 2] * 1e3:.1f} ms, longest "
              f"{durations[-1] * 1e3:.1f} ms), {len(self.sent)} requests "
              f"sent, {len(done)} finished, "
              f"{sum(len(r['stamps']) for r in self.sent)} tokens, "
              f"{sum(max(len(r['stamps']) - 1, 0) for r in self.sent)} "
              f"token gaps, in {t1 - t0:.3f} s", file=sys.stderr, flush=True)
        if not done:
            raise SystemExit("bench_cells: no request finished inside the "
                             "window; nothing to compare")
        # the sample the reference will follow: the longest finished
        # request and a few more drawn from the seed
        rng = np.random.default_rng(self.seed)
        order = sorted(range(len(done)), key=lambda i: -(
            len(done[i]["prompt"]) + done[i]["n_new"]))
        pick = order[:1] + [int(i) for i in rng.permutation(order[1:])[
            :mix["check"]["requests"] - 1]]
        self.sample = [(done[i]["prompt"],
                        np.asarray(done[i]["handle"].tokens, np.int32))
                       for i in pick]

    # -- the check ---------------------------------------------------------

    def free(self) -> None:
        del self.eng, self.current, self.sent
        gc.collect()

    def _readings(self, quant: str | None):
        """Over the sample: each served token's gap under the reference,
        and (control) the gap of the token ``quant`` precision puts first."""
        gpt, mix = self.gpt, self.mix
        n_out = mix["answer_lengths"]["max"]
        T = gpt["seq_len"]
        served_gaps, control_gaps = [], []
        with jax.default_matmul_precision("highest"):
            params = reference.stack_blocks(
                weights.init_gpt(self.seed, gpt))
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
                kw = dict(n_heads=gpt["n_heads"], n_out=n_out)
                ref = reference.served_logits(params, jnp.asarray(seq),
                                              first, **kw)
                got = ref if quant is None else reference.served_logits(
                    params, jnp.asarray(seq), first, quant=quant, **kw)
                a, b = _gaps(ref, got, jnp.asarray(served))
                served_gaps.append(np.asarray(a, np.float64)[:n])
                control_gaps.append(np.asarray(b, np.float64)[:n])
        return np.concatenate(served_gaps), np.concatenate(control_gaps)

    @staticmethod
    def _values(gaps: np.ndarray) -> dict:
        # the widest gap swings from sample to sample (sound runs read up to
        # 0.02, the control from 0.05): printed, not compared
        return {"gap_mean": float(gaps.mean())}

    def check(self) -> dict:
        self.free()
        t = time.perf_counter()
        served, _ = self._readings(None)
        self.records["reference_s"] = time.perf_counter() - t
        self.records["check_detail"] = {
            "requests": len(self.sample), "tokens": int(served.size),
            "gap_max": float(served.max()),
            "longest": int(max(len(p) + len(t_) for p, t_ in self.sample))}
        return self._values(served)

    def control(self) -> dict:
        """The control's numbers: at each position of the same prompts and
        tokens, the gap of the token the lower precision puts first."""
        _, ctrl = self._readings(
            self.cell.config["control"]["serve"]["quant"])
        self.records["check_detail"] = {"gap_max": float(ctrl.max())}
        return self._values(ctrl)
