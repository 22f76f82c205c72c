"""Request objects and the serving backpressure error hierarchy.

A :class:`Request` is the unit the multiplexer schedules: it carries the
prompt, the generation budget, the lifecycle timestamps the latency
histograms are computed from, and — while running — its slot and reserved
KV pages. The reference's analog is one AsyncExecutor DataFeed work item
(SURVEY L4); here the item is an autoregressive generation, not a
training minibatch.
"""

from __future__ import annotations

import itertools
import time
from typing import List, Optional, Sequence, Tuple

__all__ = ["Request", "BackpressureError", "DrainingError",
           "QUEUED", "RUNNING", "FINISHED", "REJECTED",
           "TIMEOUT", "FAILED"]

QUEUED = "queued"
RUNNING = "running"
FINISHED = "finished"
REJECTED = "rejected"
TIMEOUT = "timeout"    # deadline expired before completion (typed retirement)
FAILED = "failed"      # in-flight batch lost to a decode failure

_ids = itertools.count()


class BackpressureError(RuntimeError):
    """The serving stack cannot take more work RIGHT NOW (bounded queue
    full, or — via the :class:`~.page_pool.PagePoolExhausted` subclass — no
    KV pages left). Deliberately a distinct type: callers shed or retry;
    it never signals a crash. ``fault_class`` is what
    ``reliability.faults.classify`` tells it by."""

    fault_class = "backpressure"


class DrainingError(BackpressureError):
    """The engine is draining (graceful shutdown: SIGTERM, rollout) — it
    stopped admitting and will finish in-flight work then close. Unlike
    queue backpressure, retrying THIS engine is pointless; the caller
    re-routes to a peer."""


class Request:
    """One generation request.

    ``prompt`` is a sequence of int token ids; ``max_new_tokens`` bounds
    generation (the prefill's first sampled token counts toward it).
    ``deadline_s`` (optional) is a wall-clock budget from submission: a
    request past its deadline is retired with state :data:`TIMEOUT` so it
    stops pinning a slot and KV pages. ``error`` carries the failure text
    when a decode failure retires the request as :data:`FAILED`.

    Sampling (device-side, inside the fused decode scan):
    ``temperature=0`` (the default) is EXACTLY the greedy argmax path —
    bit-identical tokens, not merely close; ``temperature>0`` samples from
    the temperature-scaled distribution, restricted to the ``top_k``
    highest logits when ``top_k>0`` (0 = no restriction). ``seed`` names
    the request's private RNG stream (derived from the request id when
    None, so two requests never share one by accident); the stream is
    keyed by absolute context position, which makes replays reproducible
    across ``decode_fuse`` widths and slot re-admissions.

    ``timeline`` is the request's own record of when its tokens reached
    it: one entry a HAND-OVER, ``(t, n, prefill_clock_s)``: the
    ``time.perf_counter`` instant, ``len(tokens_out)`` after it, and the
    engine's prefill clock at that instant (the seconds the engine has
    spent inside ``serving/prefill`` spans so far). The first entry is the
    prefill's token (``t`` is ``first_token_t``); each later one is a
    decode dispatch that brought at least one token, however many, at the
    end of the sync that read it. The difference of two entries' clocks is
    what the request lost behind admissions between them (the rest of its
    own admission included: its second token waits for it). ``prefill_s``
    is the length of its own ``serving/prefill`` span, launch to slot armed
    (the prefill executable arms the slot itself, so what follows the read
    of the first token is the host's bookkeeping alone);
    ``serving/admission_ms`` observes it.
    """

    __slots__ = ("id", "prompt", "max_new_tokens", "state", "slot", "pages",
                 "group_pages",
                 "tokens_out", "submitted_t", "admitted_t", "first_token_t",
                 "finished_t", "deadline_s", "error", "trace_id", "attempt",
                 "temperature", "top_k", "seed",
                 "timeline", "prefill_s")

    def __init__(self, prompt: Sequence[int], max_new_tokens: int,
                 deadline_s: Optional[float] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 seed: Optional[int] = None,
                 trace_id: Optional[str] = None, attempt: int = 0):
        if len(prompt) == 0:
            raise ValueError("Request needs a non-empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline_s is not None and deadline_s < 0:
            raise ValueError("deadline_s must be >= 0")
        if temperature < 0:
            raise ValueError("temperature must be >= 0 (0 = greedy)")
        if top_k < 0:
            raise ValueError("top_k must be >= 0 (0 = unrestricted)")
        self.id = next(_ids)
        # The per-request trace identity: spans in the serving timeline and
        # flight-recorder batch specs carry it, so a crash dump links back
        # to the exact request lifelines in the Perfetto trace. A fleet
        # router overrides it with the FLEET trace id (stable across
        # requeues) so one cross-process timeline joins every attempt;
        # ``attempt`` (1-based, 0 = not a fleet replay) rides span args.
        self.trace_id = trace_id if trace_id else "req-%d" % self.id
        self.attempt = int(attempt)
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.state = QUEUED
        self.slot: Optional[int] = None
        # KV pages reserved at admission: ``group_pages`` one list a cache
        # group, ``pages`` the first group's (all there is in a one-group
        # cache)
        self.pages: List[int] = []
        self.group_pages: List[List[int]] = []
        self.tokens_out: List[int] = []
        self.submitted_t = time.perf_counter()
        self.admitted_t: Optional[float] = None
        self.first_token_t: Optional[float] = None
        self.finished_t: Optional[float] = None
        self.timeline: List[Tuple[float, int, float]] = []
        self.prefill_s: Optional[float] = None
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        self.error: Optional[str] = None
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        # id-derived default: distinct per request, stable for replay when
        # the caller pins one explicitly
        self.seed = int(self.id if seed is None else seed) & 0x7FFFFFFF

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def latency_s(self) -> Optional[float]:
        if self.finished_t is None:
            return None
        return self.finished_t - self.submitted_t

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submitted_t

    def expired(self, now: Optional[float] = None) -> bool:
        """True once the wall clock passed this request's deadline (always
        False without one)."""
        if self.deadline_s is None:
            return False
        if now is None:
            now = time.perf_counter()
        return now - self.submitted_t >= self.deadline_s

    def __repr__(self):
        return ("Request(id=%d, state=%s, prompt_len=%d, out=%d/%d, slot=%s)"
                % (self.id, self.state, len(self.prompt),
                   len(self.tokens_out), self.max_new_tokens, self.slot))
