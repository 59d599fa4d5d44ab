"""Batched serving driver: prefill + decode with a KV/state cache.

Serves a reduced-config model on CPU end-to-end (examples/serve_batched.py
drives it); the same step functions lower on the production meshes in the
dry-run. Continuous-batching style: a request joins at the next decode
step boundary; all requests share one cache of max_seq slots.

``--arrivals`` switches to arrival-driven serving: a seeded request
trace from the fleet plane's generators (``repro.core.fleet`` — the
same Poisson/diurnal/bursty processes that drive the 4k-chip
simulator) feeds the server epoch by epoch, requests joining at the
next epoch boundary and queuing until a full batch forms — the fleet
simulator's binning rule exercised at single-server scale.
"""
from __future__ import annotations

import argparse
import math
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig, get_arch
from repro.models import model as M
from repro.models import registry
from repro.models.param import init_params
from repro.parallel.jax_compat import use_compile_cache
from repro.parallel.sharding import BASELINE, use_rules
from repro.train.steps import make_prefill_step, make_serve_step


class Server:
    def __init__(self, arch: str, *, reduced: bool = True,
                 batch: int = 4, max_seq: int = 128, seed: int = 0):
        base = get_arch(arch)
        self.cfg = base.reduced() if reduced else base
        if self.cfg.encoder_only:
            raise ValueError("encoder-only arch has no decode step")
        self.batch = batch
        self.max_seq = max_seq
        self.params = init_params(registry.param_specs(self.cfg),
                                  jax.random.PRNGKey(seed))
        self.prefill = jax.jit(make_prefill_step(self.cfg, remat="none"))
        self.decode = jax.jit(make_serve_step(self.cfg))
        self.cache = None
        self.cache_len = 0

    def prefill_prompts(self, prompts: np.ndarray):
        """prompts: (B, S0) int32. Builds the shared cache."""
        B, S0 = prompts.shape
        assert B == self.batch
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if self.cfg.frontend == "vision":
            batch["patches"] = jnp.zeros(
                (B, self.cfg.frontend_seq, self.cfg.frontend_dim),
                jnp.bfloat16)
            S0 = S0 + self.cfg.frontend_seq
        logits, cache = self.prefill(self.params, batch)
        # graft the prefill cache into a max_seq-slot decode cache
        full = M.init_cache(self.cfg, B, self.max_seq)
        def graft(dst, src):
            if dst.shape == src.shape:  # states (ssm/conv) — same shape
                return src.astype(dst.dtype)
            # KV-like: copy the first S0 slots along the seq axis (axis 2
            # for stacked (L, B, S, ...) arrays)
            return jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), 0, axis=2)
        self.cache = jax.tree.map(graft, full, cache)
        self.cache_len = S0
        return np.asarray(jnp.argmax(logits[:, -1], axis=-1))

    def step(self, tokens: np.ndarray) -> np.ndarray:
        """tokens: (B,) int32 — the previous step's outputs."""
        batch = {"tokens": jnp.asarray(tokens, jnp.int32)[:, None],
                 "cache_len": jnp.asarray(self.cache_len, jnp.int32)}
        logits, self.cache = self.decode(self.params, self.cache, batch)
        self.cache_len += 1
        return np.asarray(jnp.argmax(logits[:, -1], axis=-1))

    def generate(self, prompts: np.ndarray, n_tokens: int) -> np.ndarray:
        out = [self.prefill_prompts(prompts)]
        for _ in range(n_tokens - 1):
            out.append(self.step(out[-1]))
        return np.stack(out, axis=1)  # (B, n_tokens)


def serve_arrivals(srv: Server, spec, *, duration_s: float,
                   epoch_s: float, prompt_len: int, n_tokens: int,
                   seed: int = 0, checkpoint: str | None = None) \
        -> list[dict]:
    """Serve a seeded arrival trace with epoch-boundary batching.

    ``spec`` is a ``repro.core.fleet.ArrivalSpec``; its per-epoch
    request counts (fixed-draw-count generators, deterministic under
    ``seed``) land on the queue at each epoch boundary, and the server
    drains the queue in full ``srv.batch``-sized waves — the remainder
    carries to the next epoch, exactly how the fleet simulator bins
    requests into epochs. Returns one stats dict per epoch.

    SIGTERM/SIGINT are handled guard-plane style (ISSUE 9): instead of
    dying mid-epoch, the in-flight wave finishes, the current epoch's
    stats are recorded (flagged ``"drained": True``), and the final
    report is emitted to the caller — plus, when ``checkpoint`` names
    a path, an atomic JSON report (``guard.atomic_write_json``) with
    the per-epoch stats and the interrupting signal, so an operator
    preempting the server still gets a crash-consistent record. The
    previous signal handlers are restored on exit either way.
    """
    from repro.core.fleet import arrival_counts
    from repro.core.guard import atomic_write_json
    n_epochs = max(1, int(math.ceil(duration_s / epoch_s)))
    rng = np.random.default_rng(seed)
    counts = arrival_counts(spec, n_epochs, epoch_s, rng)
    queue = 0
    stats: list[dict] = []
    stop: dict = {"signum": None}

    def _handler(signum, frame):
        stop["signum"] = signum

    prev = {s: signal.signal(s, _handler)
            for s in (signal.SIGTERM, signal.SIGINT)}
    try:
        for e in range(n_epochs):
            queue += int(counts[e])
            served = 0
            t0 = time.time()
            while queue >= srv.batch and stop["signum"] is None:
                prompts = rng.integers(0, srv.cfg.vocab_size,
                                       (srv.batch, prompt_len),
                                       dtype=np.int32)
                srv.generate(prompts, n_tokens)
                queue -= srv.batch
                served += srv.batch
            rec = {"epoch": e, "arrived": int(counts[e]),
                   "served": served, "queued": queue,
                   "wall_s": time.time() - t0}
            if stop["signum"] is not None:
                rec["drained"] = True
                stats.append(rec)
                break
            stats.append(rec)
    finally:
        for s, h in prev.items():
            signal.signal(s, h)
        if checkpoint is not None:
            sig = stop["signum"]
            atomic_write_json(checkpoint, {
                "epochs": stats,
                "served_total": sum(s["served"] for s in stats),
                "interrupted": (signal.Signals(sig).name
                                if sig is not None else None)})
    return stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--arrivals", choices=("poisson", "diurnal",
                                           "bursty"), default=None,
                    help="serve a seeded arrival trace (fleet-plane "
                         "generators) instead of one fixed batch")
    ap.add_argument("--rate", type=float, default=1.0,
                    help="mean arrival rate, requests/s")
    ap.add_argument("--duration", type=float, default=30.0,
                    help="arrival-trace window, seconds")
    ap.add_argument("--epoch", type=float, default=5.0,
                    help="batching epoch length, seconds")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None,
                    help="write the arrival-mode final report to this "
                         "path (atomic JSON; also written when a "
                         "SIGTERM/SIGINT drain ends the run early)")
    args = ap.parse_args(argv)
    use_compile_cache()
    with use_rules(BASELINE):
        srv = Server(args.arch, batch=args.batch,
                     max_seq=args.prompt_len + args.tokens + 8)
        if args.arrivals:
            from repro.core.fleet import ArrivalSpec
            spec = ArrivalSpec(args.arrivals, rate_rps=args.rate,
                               period_s=args.duration)
            stats = serve_arrivals(srv, spec, duration_s=args.duration,
                                   epoch_s=args.epoch,
                                   prompt_len=args.prompt_len,
                                   n_tokens=args.tokens, seed=args.seed,
                                   checkpoint=args.checkpoint)
            for s in stats:
                drain = " [drained]" if s.get("drained") else ""
                print(f"[serve] epoch {s['epoch']}: arrived "
                      f"{s['arrived']}, served {s['served']}, queued "
                      f"{s['queued']} ({s['wall_s']:.2f}s){drain}")
            tot = sum(s["served"] for s in stats)
            print(f"[serve] {tot} requests served over "
                  f"{len(stats)} epochs")
            return
        rng = np.random.default_rng(args.seed)
        prompts = rng.integers(0, srv.cfg.vocab_size,
                               (args.batch, args.prompt_len), dtype=np.int32)
        t0 = time.time()
        toks = srv.generate(prompts, args.tokens)
        dt = time.time() - t0
        print(f"[serve] {args.batch} requests x {args.tokens} tokens in "
              f"{dt:.2f}s ({args.batch*args.tokens/dt:.1f} tok/s)")
        print("[serve] outputs:", toks[:, :8].tolist())


if __name__ == "__main__":
    main()
