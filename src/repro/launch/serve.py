"""Serving driver: ragged-batch greedy decode and the slot-pool engine.

Two entry points share the model's `serve_step`:

  * `greedy_decode` / `fused_prefill` — the STATIC-batch reference path.
    Prompts may be right-padded ragged (`lengths=`): pad tokens are
    length-masked out of the cache (serve_step's `active` row mask) and
    the first generated token comes from each sequence's TRUE last prompt
    token, so a ragged batch decodes exactly like each prompt run alone
    unpadded. Prefill is fused by default (one jitted `lax.scan` over the
    prompt — a single XLA dispatch); `--prefill loop` keeps the
    token-at-a-time dispatch loop as the reference oracle.
  * `launch.engine.DecodeEngine` — continuous batching over a fixed slot
    pool: requests admitted mid-flight, one dispatch advances all live
    slots, EOS/max-token retirement and slot recycling. The CLI serves a
    ragged synthetic request set through it by default (`--mode engine`).

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --reduced \\
      --batch 4 --prompt-len 16 --min-prompt-len 4 --gen 32 --slots 3

`run(argv)` is the whole CLI as a function: it returns a `ServeRun` with
the engine it drove and the tokens it generated.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core.spec import init_params
from repro.models.transformer import build_model


def fused_prefill(model, params, prompts: jnp.ndarray, cache_len: int,
                  lengths: jnp.ndarray | None = None):
    """One jitted scan over the prompt: returns (last logits, filled cache).

    prompts: (B, P) right-padded; lengths: optional (B,) true prompt
    lengths (None means every row uses all P tokens). Pad positions are
    masked out of the cache and the returned logits are each row's TRUE
    last-token logits (float32), not `logits[-1]`.

    Call through `jax.jit` (see `greedy_decode`): the P decode steps fuse
    into one dispatch whose cache round-trips stay on device.
    """
    b, p = prompts.shape
    cache = model.init_cache(b, cache_len)
    last0 = jnp.zeros((b, model.cfg.vocab_size), jnp.float32)

    if lengths is None:
        # equal-length fast path: no row mask, plain cache writes
        def step(carry, tok):
            cache, _ = carry
            logits, cache = model.serve_step(params, cache,
                                             {"token": tok[:, None]})
            return (cache, logits.astype(jnp.float32)), None

        (cache, last), _ = jax.lax.scan(step, (cache, last0), prompts.T)
        return last, cache

    def step(carry, xs):
        cache, last = carry
        tok, t = xs
        act = t < lengths
        logits, cache = model.serve_step(
            params, cache, {"token": tok[:, None], "active": act})
        last = jnp.where(act[:, None], logits.astype(jnp.float32), last)
        return (cache, last), None

    (cache, last), _ = jax.lax.scan(
        step, (cache, last0),
        (prompts.T, jnp.arange(p, dtype=jnp.int32)))  # scan over P
    return last, cache


def _jitted(model, key, build):
    """Per-model cache of jitted serving programs, so repeat greedy_decode
    calls (examples, benchmarks) re-dispatch instead of re-tracing."""
    cache = getattr(model, "_serve_jit_cache", None)
    if cache is None:
        cache = model._serve_jit_cache = {}
    if key not in cache:
        cache[key] = jax.jit(build())
    return cache[key]


def greedy_decode(model, params, prompts: jnp.ndarray, gen: int,
                  cache_len: int, *, prefill: str = "fused",
                  lengths=None):
    """prompts: (B, P) int32, right-padded if ragged; lengths: optional
    (B,) true prompt lengths. prefill: 'fused' (single jitted scan) or
    'loop' (reference oracle: one dispatch per token — same math)."""
    b, p = prompts.shape
    if p == 0:
        raise ValueError(
            "empty prompt (P == 0): greedy_decode needs at least one prompt "
            "token per sequence — seed requests with a BOS token")
    step = _jitted(model, "step", lambda: model.serve_step)
    if prefill == "fused":
        if lengths is None:
            pf = _jitted(
                model, ("prefill", cache_len),
                lambda: lambda pr, ps: fused_prefill(model, ps, pr,
                                                     cache_len))
            logits, cache = pf(prompts, params)
        else:
            ln = jnp.asarray(lengths, jnp.int32)
            pf = _jitted(
                model, ("prefill_ragged", cache_len),
                lambda: lambda pr, l, ps: fused_prefill(model, ps, pr,
                                                        cache_len, l))
            logits, cache = pf(prompts, ln, params)
    else:
        cache = model.init_cache(b, cache_len)
        logits = jnp.zeros((b, model.cfg.vocab_size), jnp.float32)
        ln = (None if lengths is None
              else jnp.asarray(lengths, jnp.int32))
        for t in range(p):
            if ln is None:  # equal-length fast path: no row mask
                lg, cache = step(params, cache,
                                 {"token": prompts[:, t:t + 1]})
                logits = lg.astype(jnp.float32)
                continue
            act = jnp.full((b,), t, jnp.int32) < ln
            lg, cache = step(params, cache,
                             {"token": prompts[:, t:t + 1], "active": act})
            # true-last-token gather: only rows still inside their prompt
            # update, so the final value is each row's length-1 logits
            logits = jnp.where(act[:, None], lg.astype(jnp.float32), logits)
    if gen <= 0:
        return jnp.zeros((b, 0), jnp.int32)
    out = []
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    for _ in range(gen):
        out.append(tok)
        logits, cache = step(params, cache, {"token": tok})
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


def build_serve_parser() -> argparse.ArgumentParser:
    """The serve CLI's argument surface (importable so tests/docs can
    introspect it — tests/test_docs.py asserts every flag is documented)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny", choices=ARCH_IDS + ["tiny"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None, metavar="N",
                    help="keep only the first N layers of the config "
                         "(depth cut; every width stays as published)")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests in the synthetic set")
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="maximum prompt length")
    ap.add_argument("--min-prompt-len", type=int, default=None,
                    help="minimum prompt length (default = --prompt-len, "
                         "i.e. an equal-length batch; set lower for a "
                         "ragged request set)")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mode", default="engine", choices=["engine", "batch"],
                    help="engine: continuous-batching slot pool "
                         "(launch.engine.DecodeEngine); batch: the static "
                         "padded-batch greedy_decode reference")
    ap.add_argument("--slots", type=int, default=None,
                    help="engine slot-pool size (default = --batch)")
    ap.add_argument("--prefill", default="fused", choices=["fused", "loop"],
                    help="batch mode: fused = single jitted scan over the "
                         "prompt (one dispatch); loop = reference "
                         "token-at-a-time oracle")
    ap.add_argument("--paging", default="auto", choices=["auto", "on", "off"],
                    help="engine mode KV data plane: auto pages "
                         "full-attention families (block pool + page "
                         "tables + prefix sharing), off keeps per-slot "
                         "contiguous caches, on forces paging")
    ap.add_argument("--page-len", type=int, default=16,
                    help="tokens per physical KV page")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool size (default: slots * cache pages, "
                         "i.e. the contiguous footprint; set lower to "
                         "exercise eviction/spill)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many common system-prompt tokens "
                         "to every request; full pages of it are shared "
                         "physically when paging is on")
    ap.add_argument("--backend", default="auto",
                    choices=["xla", "pallas", "auto"],
                    help="decode-attention engine scope "
                         "(repro.kernels.backend); auto resolves the "
                         "paged-attention path from the measured autotune "
                         "table, falling back to xla off-TPU")
    ap.add_argument("--autotune", default="on", choices=["on", "off"],
                    help="on: auto consults the measured table for this "
                         "topology (repro.kernels.autotune)")
    ap.add_argument("--cache", default="on", choices=["on", "off"],
                    help="persistent compilation cache: warm starts "
                         "deserialize the serving programs instead of "
                         "recompiling (repro.launch.compile_cache)")
    ap.add_argument("--cache-dir", default=None,
                    help="cache root (default <repo>/.cache or "
                         "$REPRO_CACHE_DIR)")
    ap.add_argument("--tenants", type=int, default=None,
                    help="serve multi-tenant: adapter-slot count of the "
                         "tenant-stacked DP-LoRA buffer (engine mode only; "
                         "implies --lora-rank > 0); requests round-robin "
                         "over the tenants")
    ap.add_argument("--lora-rank", type=int, default=4,
                    help="adapter rank for multi-tenant serving (must "
                         "match the rank the adapters were trained at)")
    ap.add_argument("--adapter-dir", action="append", default=None,
                    metavar="DIR",
                    help="publish directory of a training service "
                         "(<service_dir>/publish) to load tenant adapters "
                         "from; repeatable — one tenant per directory, "
                         "extra tenants (up to --tenants) serve the base "
                         "model")
    ap.add_argument("--watch", action="store_true",
                    help="poll each --adapter-dir between pool steps and "
                         "hot-swap newly published adapters into the live "
                         "engine (launch.swap.AdapterWatcher)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


@dataclasses.dataclass
class ServeRun:
    """What one run of the serving CLI did."""

    cfg: Any
    engine: Any  # the DecodeEngine (None in --mode batch)
    requests: list  # the prompts served, shared prefix included
    tokens: np.ndarray  # (requests, gen) generated ids, -1 past the end


def run(argv=None) -> ServeRun:
    """Parse `argv` like the CLI and serve; returns what the run did."""
    args = build_serve_parser().parse_args(argv)

    from repro.launch.train import (config_from_args, record_cache_program,
                                    setup_caches)
    setup_caches(args)

    cfg = config_from_args(args)
    if args.tenants is not None:
        if args.mode != "engine":
            raise SystemExit("--tenants requires --mode engine")
        cfg = dataclasses.replace(cfg, lora_rank=args.lora_rank)
    model = build_model(cfg)
    params = init_params(model.spec, jax.random.PRNGKey(args.seed))
    record_cache_program(args, entry="serve", arch=cfg.name)

    from repro.launch.inputs import pad_ragged_prompts, synthetic_requests
    lo = (args.prompt_len if args.min_prompt_len is None
          else args.min_prompt_len)
    reqs = synthetic_requests(cfg.vocab_size, args.batch, min_len=lo,
                              max_len=args.prompt_len, seed=1)
    if args.shared_prefix:
        rng = np.random.RandomState(args.seed + 100)
        sysp = rng.randint(1, cfg.vocab_size,
                           args.shared_prefix).astype(np.int32)
        reqs = [np.concatenate([sysp, np.asarray(r, np.int32)])
                for r in reqs]
    cache_len = args.shared_prefix + args.prompt_len + args.gen + 8

    # scoped engine: the serving traces capture the backend (and its
    # autotune consultation) statically, exactly like the train step
    from contextlib import ExitStack

    from repro.kernels import backend as KB
    scope = ExitStack()
    scope.enter_context(KB.scoped(args.backend,
                                  autotune=args.autotune != "off"))

    eng = None
    t0 = time.time()
    if args.mode == "engine":
        from repro.launch.engine import DecodeEngine
        num_slots = args.batch if args.slots is None else args.slots
        if args.paging != "off":
            # paging needs cache_len % page_len == 0 (that divisibility is
            # what makes the paged plane bitwise-identical); round up
            cache_len = -(-cache_len // args.page_len) * args.page_len
        eng = DecodeEngine(model, params, num_slots=num_slots,
                           cache_len=cache_len, paging=args.paging,
                           page_len=args.page_len, num_pages=args.num_pages,
                           max_tenants=args.tenants)
        watchers = []
        tids = [None]
        if args.tenants is not None:
            from repro.launch.swap import AdapterWatcher
            tids = [eng.add_tenant(name=f"tenant-{i}")
                    for i in range(args.tenants)]
            for tid, d in zip(tids, args.adapter_dir or []):
                w = AdapterWatcher(eng, tid, d)
                got = w.poll()  # install whatever is already published
                print(f"# tenant {tid} <- {d}: "
                      f"{'step ' + str(got.step) if got else 'base model'}")
                watchers.append(w)
        for i, r in enumerate(reqs):
            eng.submit(r, max_new_tokens=args.gen,
                       tenant=tids[i % len(tids)])
        if args.watch and watchers:
            # pump the pool in short bursts, polling the publish dirs in
            # the gaps — a swap lands between dispatches, never inside one
            done = {}
            while eng.num_pending or eng.num_live:
                eng.run(max_steps=8)
                for w in watchers:
                    got = w.poll()
                    if got is not None:
                        print(f"# hot swap: tenant {got.tenant} -> step "
                              f"{got.step} (v{got.version}, bitwise ok)")
            done = eng.completions()
        else:
            done = eng.run()
        wall = time.time() - t0
        toks = np.full((args.batch, args.gen), -1, np.int32)
        for rid, c in done.items():
            toks[rid, :len(c.tokens)] = c.tokens
        extra = (f"slots={eng.num_slots} "
                 f"dispatches={eng.stats['decode_dispatches']}d"
                 f"+{eng.stats['prefill_dispatches']}p "
                 f"paged={'yes' if eng.paged else 'no'}")
        if eng.multi_tenant:
            extra += (f" tenants={len(tids)} "
                      f"swaps={eng.stats['adapter_swaps']} "
                      f"traces={sum(eng.trace_counts.values())}")
            for tid in tids:
                ts = eng.tenant_stats(tid)
                print(f"# tenant {tid} ({ts['name']}): v{ts['version']} "
                      f"done={ts['requests_done']} "
                      f"tokens={ts['tokens_out']}")
        if eng.paged:
            s = eng.stats
            extra += (f" pages={eng.num_pages}x{eng.page_len} "
                      f"peak_pages={s['peak_pages_in_use']} "
                      f"prefix_hits={s['prefix_hits']} "
                      f"shared={s['shared_pages']} "
                      f"evicted={s['evicted_pages']} "
                      f"readmitted={s['readmitted_pages']} "
                      f"cache_mb={eng.cache_bytes() / 2**20:.1f}")
    else:
        prompts, lengths = pad_ragged_prompts(reqs)
        toks = np.asarray(greedy_decode(
            model, params, jnp.asarray(prompts), args.gen, cache_len,
            prefill=args.prefill, lengths=jnp.asarray(lengths)))
        wall = time.time() - t0
        extra = f"prefill={args.prefill}"
    scope.close()
    total = sum(len(r) for r in reqs) + args.batch * args.gen
    print(f"# arch={cfg.name} mode={args.mode} batch={args.batch} "
          f"prompt_lens={[len(r) for r in reqs]} {extra} "
          f"generated {args.gen} tokens/seq in {wall:.2f}s "
          f"({total / wall:.1f} tok/s incl. prefill)")
    print(toks[:, :16])
    return ServeRun(cfg=cfg, engine=eng, requests=reqs, tokens=toks)


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
