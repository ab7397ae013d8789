"""Batched serving engine: length-bucketed scheduler + prefill/decode loop.

Requests are grouped into equal-prompt-length buckets (the scheduler pads
the tail batch), each bucket runs one prefill then greedy/temperature
decode against the cache pytree.  Throughput metrics (prefill tokens/s,
decode steps/s) are reported per bucket — the serving-side face of the
paper's pipeline: prompt tokens stream out of TabFiles through the
configured scan, and the decode loop overlaps host batch assembly with
device steps via async dispatch.

This module also hosts the **multi-tenant query front end**
(:class:`QueryFrontEnd`, DESIGN.md §11): a session API — ``submit`` /
``poll`` / ``cancel`` with tenant identity — over a ScanService
configured for serving (weighted fair shares, admission control, a
delivered-result window) plus a process-level fragment result cache.
Queries route through q6/q12 and the dataset executor exactly as the
library paths do; the front end only adds tenancy, ticketing, and
caching on top.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.models.model import Model


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (L,) int32
    max_new_tokens: int = 32
    eos_id: int | None = None


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: np.ndarray
    prefill_seconds: float
    decode_seconds: float


class ServeEngine:
    def __init__(self, model: Model, params, max_batch: int = 8,
                 max_seq: int = 512, temperature: float = 0.0,
                 seed: int = 0):
        if model.cfg.encoder_only:
            raise ValueError("encoder-only archs are not served")
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.temperature = temperature
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)
        self._rng = jax.random.PRNGKey(seed)

    def _sample(self, logits: jnp.ndarray) -> jnp.ndarray:
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self._rng, k = jax.random.split(self._rng)
        return jax.random.categorical(
            k, logits / self.temperature, axis=-1).astype(jnp.int32)

    def _run_bucket(self, requests: list[Request]) -> list[Completion]:
        b = len(requests)
        lp = requests[0].prompt.shape[0]
        assert all(r.prompt.shape[0] == lp for r in requests)
        prompts = jnp.asarray(np.stack([r.prompt for r in requests]),
                              jnp.int32)
        max_new = max(r.max_new_tokens for r in requests)
        caches = self.model.init_caches(b, min(self.max_seq,
                                               lp + max_new + 1))
        t0 = time.perf_counter()
        logits, caches = self._prefill(self.params, {"tokens": prompts},
                                       caches)
        logits.block_until_ready()
        t_prefill = time.perf_counter() - t0

        out = np.zeros((b, max_new), dtype=np.int32)
        tok = self._sample(logits)[:, None]
        t0 = time.perf_counter()
        for i in range(max_new):
            out[:, i] = np.asarray(tok[:, 0])
            if i == max_new - 1:
                break
            logits, caches = self._decode(
                self.params, tok, jnp.asarray(lp + i, jnp.int32), caches)
            tok = self._sample(logits)[:, None]
        t_decode = time.perf_counter() - t0

        completions = []
        for j, r in enumerate(requests):
            toks = out[j, :r.max_new_tokens]
            if r.eos_id is not None:
                stop = np.flatnonzero(toks == r.eos_id)
                if stop.size:
                    toks = toks[:stop[0] + 1]
            completions.append(Completion(r.uid, toks, t_prefill, t_decode))
        return completions

    def generate(self, requests: list[Request]) -> dict[int, Completion]:
        """Length-bucketed batch scheduling."""
        buckets: dict[int, list[Request]] = {}
        for r in requests:
            buckets.setdefault(r.prompt.shape[0], []).append(r)
        results: dict[int, Completion] = {}
        for _, reqs in sorted(buckets.items()):
            for i in range(0, len(reqs), self.max_batch):
                chunk = reqs[i:i + self.max_batch]
                for c in self._run_bucket(chunk):
                    results[c.uid] = c
        return results

    def throughput_report(self, completions: dict[int, Completion]) -> dict:
        n_prompt = sum(c.tokens.shape[0] for c in completions.values())
        total_decode = sum(c.decode_seconds for c in completions.values())
        total_prefill = sum(c.prefill_seconds for c in completions.values())
        return {
            "n_requests": len(completions),
            "prefill_seconds": total_prefill,
            "decode_seconds": total_decode,
            "new_tokens": int(n_prompt),
            "decode_tokens_per_s": n_prompt / max(1e-9, total_decode),
        }


# ---------------------------------------------------------------------------
# multi-tenant query front end (DESIGN.md §11)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class QueryTicket:
    """One submitted query's lifecycle record.  ``state`` walks
    queued → running → done | failed | rejected | cancelled; the stamps
    are ``time.perf_counter()`` seconds, the flight recorder's clock."""

    id: str
    tenant: str
    query: str
    state: str = "queued"
    result: object = None
    reports: tuple = ()
    error: BaseException | None = None
    submitted_at: float = 0.0
    started_at: float = 0.0
    finished_at: float = 0.0

    @property
    def finished(self) -> bool:
        return self.state in ("done", "failed", "rejected", "cancelled")


class QueryFrontEnd:
    """Session API over the multi-tenant ScanService: ``submit`` /
    ``poll`` / ``cancel`` with tenant identity.

    The front end owns (unless given) a ScanService with the
    delivered-result window enabled and a process-level
    FragmentResultCache, and routes every query through the library
    paths — ``q6``/``q12`` and the dataset executor — with
    ``tenant=``/``result_cache=`` attached.  Tenants are registered with
    a fair-share ``weight``, an admission bound ``max_active`` (typed
    rejection or queueing), and an optional ``slo_s`` latency target
    feeding the adaptive pool sizer.  Each submitted query runs on its
    own thread; ``cancel`` is best-effort — a queued ticket never runs,
    a running ticket's result is discarded at completion.

    A query served while a JAX profiler session runs turns the flight
    recorder on (``trace.follow_profiler``), records its ``queued`` span
    and emits a clock anchor as it starts; ``shutdown`` turns such a
    recorder off."""

    DEFAULT_WINDOW_BYTES = 64 << 20

    def __init__(self, service=None,
                 window_bytes: int = DEFAULT_WINDOW_BYTES,
                 result_cache=None, workers: int | None = None):
        from repro.core.scheduler import ScanService
        from repro.dataset.result_cache import FragmentResultCache
        self._own_service = service is None
        self._service = service if service is not None else \
            ScanService(workers=workers, window_bytes=window_bytes)
        self.result_cache = (result_cache if result_cache is not None
                             else FragmentResultCache())
        self._lock = threading.Lock()
        self._tickets: dict[str, QueryTicket] = {}
        self._threads: dict[str, threading.Thread] = {}
        self._ids = itertools.count(1)
        self._shutdown = False

    @property
    def service(self):
        return self._service

    def register_tenant(self, name: str, weight: int = 1,
                        max_active: int | None = None,
                        on_limit: str = "reject",
                        slo_s: float | None = None):
        return self._service.register_tenant(
            name, weight=weight, max_active=max_active,
            on_limit=on_limit, slo_s=slo_s)

    def submit(self, tenant: str, query: str, source,
               **query_kwargs) -> str:
        """Submit one query for ``tenant``; returns a ticket id.

        ``query`` is ``"q6"`` (source: a Scanner or Dataset) or
        ``"q12"`` (source: a ``(lineitem, orders)`` pair).  Extra
        keyword arguments forward to the query function.  Admission
        happens inside the query's scan submission: a tenant at its
        bound with ``on_limit="reject"`` lands the ticket in state
        ``rejected``; ``"queue"`` keeps it ``running`` until a slot
        frees."""
        if query not in ("q6", "q12"):
            raise ValueError(f"unknown query {query!r}")
        with self._lock:
            if self._shutdown:
                raise RuntimeError("QueryFrontEnd is shut down")
            tid = f"t{next(self._ids)}"
            ticket = QueryTicket(id=tid, tenant=tenant, query=query,
                                 submitted_at=time.perf_counter())
            self._tickets[tid] = ticket
            t = threading.Thread(
                target=self._run, args=(ticket, source, query_kwargs),
                daemon=True, name=f"frontend-{tenant}-{tid}")
            self._threads[tid] = t
        t.start()
        return tid

    def _run(self, ticket: QueryTicket, source, kwargs) -> None:
        from repro.core.query import q6, q12
        from repro.core.scheduler import AdmissionRejected
        with self._lock:
            if ticket.state == "cancelled":
                return
            ticket.state = "running"
            ticket.started_at = time.perf_counter()
        tr = trace.follow_profiler()
        if tr is not None:
            tr.complete("queued", "frontend", ticket.submitted_at,
                        ticket.started_at, ticket=ticket.id,
                        tenant=ticket.tenant)
            trace.clock_anchor()
        try:
            if ticket.query == "q6":
                acc, report = q6(source, service=self._service,
                                 tenant=ticket.tenant,
                                 result_cache=self.result_cache, **kwargs)
                result, reports = acc, (report,)
            else:
                line, orders = source
                res, br, pr = q12(line, orders, service=self._service,
                                  tenant=ticket.tenant,
                                  result_cache=self.result_cache,
                                  **kwargs)
                result, reports = res, (br, pr)
        except AdmissionRejected as e:
            with self._lock:
                if ticket.state != "cancelled":
                    ticket.state = "rejected"
                    ticket.error = e
                ticket.finished_at = time.perf_counter()
            return
        except BaseException as e:  # noqa: BLE001 — surfaced via poll
            with self._lock:
                if ticket.state != "cancelled":
                    ticket.state = "failed"
                    ticket.error = e
                ticket.finished_at = time.perf_counter()
            return
        with self._lock:
            if ticket.state != "cancelled":   # cancelled → discard result
                ticket.result = result
                ticket.reports = reports
                ticket.state = "done"
            ticket.finished_at = time.perf_counter()

    def poll(self, ticket_id: str) -> dict:
        """Non-blocking status: ``state``, ``result`` (when done),
        ``error`` (repr, when failed/rejected), and the wall so far."""
        with self._lock:
            ticket = self._tickets[ticket_id]
            end = (ticket.finished_at if ticket.finished
                   else time.perf_counter())
            return {
                "id": ticket.id, "tenant": ticket.tenant,
                "query": ticket.query, "state": ticket.state,
                "result": ticket.result,
                "error": (repr(ticket.error)
                          if ticket.error is not None else None),
                "wall_s": max(0.0, end - ticket.submitted_at),
            }

    def result(self, ticket_id: str, timeout: float | None = None):
        """Block until the ticket finishes; returns ``(result, reports)``
        or re-raises the query's error (AdmissionRejected included)."""
        t = self._threads.get(ticket_id)
        if t is not None:
            t.join(timeout)
        with self._lock:
            ticket = self._tickets[ticket_id]
            if not ticket.finished:
                raise TimeoutError(f"ticket {ticket_id} still "
                                   f"{ticket.state}")
            if ticket.error is not None:
                raise ticket.error
            if ticket.state == "cancelled":
                raise RuntimeError(f"ticket {ticket_id} was cancelled")
            return ticket.result, ticket.reports

    def cancel(self, ticket_id: str) -> bool:
        """Best-effort cancel; True when the ticket had not finished.
        A queued ticket never runs; a running ticket's result is
        discarded when its thread completes."""
        with self._lock:
            ticket = self._tickets[ticket_id]
            if ticket.finished:
                return False
            ticket.state = "cancelled"
            ticket.finished_at = time.perf_counter()
            return True

    def tickets(self, tenant: str | None = None) -> list[dict]:
        with self._lock:
            ids = [t.id for t in self._tickets.values()
                   if tenant is None or t.tenant == tenant]
        return [self.poll(i) for i in ids]

    def shutdown(self, timeout: float = 10.0) -> None:
        with self._lock:
            self._shutdown = True
            threads = list(self._threads.values())
        for t in threads:
            t.join(timeout)
        trace.stop_following()
        if self._own_service:
            self._service.shutdown()

    def __enter__(self) -> "QueryFrontEnd":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
