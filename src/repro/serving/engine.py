"""Batched serving engine: continuous batched decode with the paper's
feature codec applied at the split layer.

Slots hold independent requests; each engine step decodes one token for
every active slot (static-shape friendly).  Finished slots are *refilled
from the queue mid-flight*: a freed slot gets the next queued request
prefilled (batch-1, left-padded to the batch's current absolute length so
its cache positions line up with the shared position counter) and
scattered into the batched cache, so short requests free capacity instead
of holding the batch until the longest request finishes.  When every slot
is idle the engine starts a fresh epoch with a full-batch prefill (which
also admits prompts longer than the current position).

The codec path reports bits/element of the split-layer transfer per step,
and each request's first token is stamped (``Request.t_first``) and feeds
the ``repro_engine_ttft_seconds`` histogram.

With a host codec (``codec_host_fn``) every boundary crossing is traced
as three spans sharing a ``crossing`` number -- ``split_head`` (head
dispatch and the boundary fetch), ``split_roundtrip`` (the host codec
call) and ``split_tail`` (the reconstruction's transfer and the tail
dispatch) -- and every step's host read of the sampled tokens as
``token_read``; each carries ``kind`` (``prefill``/``decode``).
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import logging
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.codec import FeatureCodec
from ..models import (decode_from_boundary, decode_step, decode_to_boundary,
                      init_cache, prefill, prefill_from_boundary,
                      prefill_to_boundary)
from ..obs.metrics import BPE_BUCKETS, MetricsRegistry
from ..obs.tracing import span

log = logging.getLogger(__name__)


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int = 16
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    t_admit: float | None = None
    t_first: float | None = None    # first generated token appended
    t_done: float | None = None

    @property
    def ttft_s(self) -> float | None:
        if self.t_admit is None or self.t_first is None:
            return None
        return self.t_first - self.t_admit

    @property
    def latency_s(self) -> float | None:
        if self.t_admit is None or self.t_done is None:
            return None
        return self.t_done - self.t_admit


def _named_jit(name: str, fn):
    """``jax.jit(fn)`` whose program is named ``jit_<name>``."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_seq: int = 256, ctx=None, codec_fn=None,
                 codec: FeatureCodec | None = None, codec_host_fn=None,
                 refill_align: int = 1,
                 metrics: MetricsRegistry | None = None):
        """``codec`` is the preferred split-layer hookup: a calibrated
        :class:`FeatureCodec` (any granularity/backend) whose fused
        fake-quant + rate estimate is applied at the boundary.  The raw
        ``codec_fn`` callable ``x -> (x', rate_bits)`` remains for custom
        transforms.

        ``codec_host_fn`` is the *host round-trip* variant for codecs
        that leave jax entirely (socket transports, subprocess codecs):
        a plain ``numpy (B, S, d) -> (numpy recon, rate_bits)`` callable.
        The engine then compiles each stage as two jitted halves split
        at the collaborative-intelligence boundary and runs the callable
        eagerly between them.  Unlike an ``io_callback`` codec_fn, no
        host work ever executes beneath an in-flight jitted program --
        which deadlocks on a single-CPU host when the callback itself
        dispatches jax computations (the callback holds XLA's only
        dispatch thread while the nested work waits for it).

        ``refill_align``: admit mid-epoch refills only at positions that
        are multiples of this.  Every refill prefills at the current
        absolute length, so each *distinct* length jit-compiles once;
        raising the alignment bounds the compile set to
        ``max_seq / refill_align`` at the cost of freed slots idling up
        to ``refill_align - 1`` steps.

        ``metrics``: a :class:`MetricsRegistry` to register this engine's
        instruments in (fresh per engine by default, so tests and
        co-hosted engines never share series)."""
        self.cfg, self.params, self.ctx = cfg, params, ctx
        if sum(x is not None for x in (codec, codec_fn, codec_host_fn)) > 1:
            raise ValueError("pass at most one of codec, codec_fn, "
                             "codec_host_fn")
        if codec is not None:
            codec_fn = codec.apply_with_rate
        self.codec_fn = codec_fn
        self.codec_host_fn = codec_host_fn
        self.slots = slots
        self.max_seq = max_seq
        self.refill_align = max(1, refill_align)
        self._finite = True
        self.rate_log: collections.deque = collections.deque(maxlen=1 << 16)
        self._crossings = itertools.count()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        m = self.metrics
        self._m = {
            "steps": m.counter("repro_engine_steps_total",
                               "batched decode steps"),
            "slot_steps": m.counter("repro_engine_slot_steps_total",
                                    "slots * decode steps"),
            "active_slot_steps": m.counter(
                "repro_engine_active_slot_steps_total",
                "decode steps weighted by occupied slots"),
            "prefills": m.counter("repro_engine_prefills_total",
                                  "prefill launches (epochs + refills)"),
            "refills": m.counter("repro_engine_refills_total",
                                 "mid-epoch slot refills"),
            "epochs": m.counter("repro_engine_epochs_total",
                                "full-batch prefill epochs"),
        }
        self._m_requests = m.counter("repro_engine_requests_total",
                                     "requests retired")
        self._m_ttft = m.histogram(
            "repro_engine_ttft_seconds",
            "time to first token (admit -> first token appended)")
        self._m_bpe = m.histogram(
            "repro_engine_split_rate_bpe",
            "split-layer coded bits/element per decode step",
            buckets=BPE_BUCKETS)

        # each program is named after the model function it runs
        # (``jit_decode_to_boundary``, ...), so profiles and compiled
        # modules tell the halves apart
        if codec_host_fn is not None:
            self._prefill_pre = _named_jit(
                "prefill_to_boundary",
                lambda p, t, c: prefill_to_boundary(cfg, p, t, c, ctx=ctx))
            self._prefill_post = _named_jit(
                "prefill_from_boundary",
                lambda p, x, c: prefill_from_boundary(cfg, p, x, c, ctx=ctx))
            self._decode_pre = _named_jit(
                "decode_to_boundary",
                lambda p, t, c, pos: decode_to_boundary(cfg, p, t, c, pos,
                                                        ctx=ctx))
            self._decode_post = _named_jit(
                "decode_from_boundary",
                lambda p, x, c, pos: decode_from_boundary(cfg, p, x, c, pos,
                                                          ctx=ctx))
            self._prefill = self._split_prefill
            self._decode = self._split_decode
        else:
            self._prefill = _named_jit(
                "prefill",
                lambda p, t, c: prefill(cfg, p, t, c, ctx=ctx,
                                        codec_fn=codec_fn))
            self._decode = _named_jit(
                "decode_step",
                lambda p, t, c, pos: decode_step(cfg, p, t, c, pos, ctx=ctx,
                                                 codec_fn=codec_fn))

    def _cross(self, kind, head, tail):
        """One boundary crossing: ``head()`` -> boundary tensor, the host
        codec round trip, ``tail(recon)``."""
        n = next(self._crossings)
        with span("split_head", kind=kind, crossing=n):
            x, pre = head()
            x = np.asarray(x, np.float32)
        with span("split_roundtrip", kind=kind, crossing=n):
            recon, rate = self.codec_host_fn(x)
        with span("split_tail", kind=kind, crossing=n):
            logits, post = tail(jnp.asarray(recon))
        return logits, list(pre) + list(post), rate

    def _split_prefill(self, p, toks, cache):
        """Prefill as two jitted halves with the host codec round-trip
        run eagerly in between (``codec_host_fn`` mode)."""
        logits, cache, _ = self._cross(
            "prefill", lambda: self._prefill_pre(p, toks, cache),
            lambda recon: self._prefill_post(p, recon, cache))
        return logits, cache

    def _split_decode(self, p, cur, cache, pos):
        logits, cache, rate = self._cross(
            "decode", lambda: self._decode_pre(p, cur, cache, pos),
            lambda recon: self._decode_post(p, recon, cache, pos))
        return logits, cache, {"codec_rate_bits": np.float32(rate)}

    # -- scheduling -----------------------------------------------------------

    def generate(self, requests: list[Request], greedy: bool = True):
        """Run all requests to completion (continuous batching with slot
        refill)."""
        for r in requests:
            if len(r.prompt) + r.max_new_tokens > self.max_seq:
                raise ValueError(
                    f"request needs {len(r.prompt) + r.max_new_tokens} "
                    f"cache positions, engine has max_seq={self.max_seq}")
        queue = list(requests)
        self._finite = True
        active: list[Request | None] = [None] * self.slots
        cache = None
        cur = None          # (slots,) next token per slot
        cur_kind = None     # the step that sampled ``cur``
        pos = 0             # shared absolute position of the next decode

        while queue or any(r is not None for r in active):
            if all(r is None for r in active):
                cache, cur, pos = self._start_epoch(queue, active)
                cur_kind = "prefill"
                continue
            # one decode step for every slot (finished/empty slots ride
            # along; their logits are ignored)
            with span("token_read", kind=cur_kind):
                for i, r in enumerate(active):
                    if r is None:
                        continue
                    if len(r.out_tokens) < r.max_new_tokens:
                        self._append_token(r, int(cur[i]))
                    if len(r.out_tokens) >= r.max_new_tokens:
                        self._retire(active, i)
            if all(r is None for r in active) and not queue:
                break
            if pos % self.refill_align == 0:
                for i in range(self.slots):
                    if active[i] is None and queue:
                        cache, cur = self._refill(queue, active, i, cache,
                                                  cur, pos)
            if all(r is None for r in active):
                continue    # nothing admitted (prompts too long for pos)
            self._m["steps"].inc()
            self._m["slot_steps"].inc(self.slots)
            self._m["active_slot_steps"].inc(sum(
                r is not None for r in active))
            lg, cache, aux = self._decode(self.params, cur, cache,
                                          jnp.int32(pos))
            if "codec_rate_bits" in aux:
                bpe = float(aux["codec_rate_bits"])
                self.rate_log.append(bpe)
                self._m_bpe.observe(bpe)
            self._finite &= jnp.all(jnp.isfinite(lg))
            cur = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            cur_kind = "decode"
            pos += 1
        return requests

    def _append_token(self, r: Request, token: int) -> None:
        r.out_tokens.append(token)
        if r.t_first is None:
            r.t_first = time.perf_counter()
            self._m_ttft.observe(r.t_first - r.t_admit)

    @property
    def logits_finite(self) -> bool:
        """Were all logits of the last ``generate`` finite?  (Reduced on
        device as the steps run; reading it syncs once.)"""
        return bool(self._finite)

    def _retire(self, active: list, i: int) -> None:
        r = active[i]
        r.done = True
        r.t_done = time.perf_counter()
        self._m_requests.inc()
        log.info("request done: slot=%d prompt_len=%d tokens=%d "
                 "latency=%.3fs", i, len(r.prompt), len(r.out_tokens),
                 r.latency_s)
        active[i] = None

    def _admissible(self, r: Request, plen: int) -> bool:
        """Can ``r`` be prefilled at padded length ``plen``?"""
        return len(r.prompt) <= plen \
            and plen + r.max_new_tokens <= self.max_seq

    @property
    def counters(self) -> dict:
        """Structured serving metrics (the observability satellite):
        slot occupancy of the continuous batch, admission churn and the
        split-layer rate actually spent.  The same numbers live as
        ``repro_engine_*`` instruments in :attr:`metrics`, beside the
        time-to-first-token histogram."""
        t = {k: int(c.value()) for k, c in self._m.items()}
        return {
            **t,
            "batch_occupancy_avg": (t["active_slot_steps"]
                                    / max(t["slot_steps"], 1)),
            "split_bpe_avg": (float(np.mean(self.rate_log))
                              if self.rate_log else 0.0),
            "requests_done": int(self._m_requests.value()),
        }

    def _start_epoch(self, queue: list, active: list):
        """Full-batch prefill of up to ``slots`` queued requests."""
        batch = [queue.pop(0) for _ in range(min(self.slots, len(queue)))]
        plen = max(len(r.prompt) for r in batch)
        toks = np.zeros((self.slots, plen), np.int32)
        t_admit = time.perf_counter()
        for i, r in enumerate(batch):
            toks[i, plen - len(r.prompt):] = r.prompt  # left-pad with 0
            r.t_admit = t_admit
            active[i] = r
        cache = init_cache(self.cfg, batch=self.slots, max_seq=self.max_seq,
                           split=self.codec_fn is not None
                           or self.codec_host_fn is not None)
        self._m["epochs"].inc()
        self._m["prefills"].inc()
        with span("prefill", batch=len(batch)):
            logits, cache = self._prefill(self.params, jnp.asarray(toks),
                                          cache)
        self._finite &= jnp.all(jnp.isfinite(logits))
        cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # zero-token requests retire immediately
        for i, r in enumerate(batch):
            if r.max_new_tokens <= 0:
                self._retire(active, i)
        return cache, cur, plen

    def _refill(self, queue: list, active: list, slot: int, cache, cur,
                pos: int):
        """Admit the next queued request into a freed slot mid-epoch.

        The prompt is left-padded to the batch's current absolute length
        ``pos`` and prefilled at batch size 1, then its cache is scattered
        into the batched cache (batch is axis 1 of every cache leaf --
        leaves are stacked (n_periods, batch, ...)), so the shared
        position counter stays valid for every slot.  Requests whose
        prompt is longer than ``pos`` (or that would overflow ``max_seq``)
        wait for a fresh epoch.
        """
        k = next((j for j, r in enumerate(queue)
                  if self._admissible(r, pos)), None)
        if k is None:
            return cache, cur
        r = queue.pop(k)
        if r.max_new_tokens <= 0:
            r.t_admit = time.perf_counter()
            active[slot] = r
            self._retire(active, slot)
            return cache, cur
        toks = np.zeros((1, pos), np.int32)
        toks[0, pos - len(r.prompt):] = r.prompt
        one = init_cache(self.cfg, batch=1, max_seq=self.max_seq,
                         split=self.codec_fn is not None
                         or self.codec_host_fn is not None)
        r.t_admit = time.perf_counter()
        self._m["refills"].inc()
        self._m["prefills"].inc()
        with span("prefill", batch=1, refill=True):
            logits, one = self._prefill(self.params, jnp.asarray(toks), one)
        self._finite &= jnp.all(jnp.isfinite(logits))
        cache = jax.tree.map(lambda full, o: full.at[:, slot].set(o[:, 0]),
                             cache, one)
        first = jnp.argmax(logits[0]).astype(jnp.int32)
        cur = cur.at[slot].set(first)
        active[slot] = r
        # this iteration's append phase already ran, so the refilled
        # request's first generated token is recorded here (it is fed to
        # the model at this iteration's decode); the next append phase
        # then records token two
        with span("token_read", kind="prefill"):
            self._append_token(r, int(first))
        if len(r.out_tokens) >= r.max_new_tokens:
            self._retire(active, slot)
        return cache, cur