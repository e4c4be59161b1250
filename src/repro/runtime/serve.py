"""Serving runtime: legacy host-loop generate + the device-resident
continuous-batching decode engine (DESIGN.md §13).

Two execution paths share the model code in :mod:`repro.models.lm`:

* :func:`generate` — the HOST loop: one Python iteration and one
  device->host sync per token. After this module's fixes it is
  deterministic past ``eos`` (finished rows emit the eos/pad id, not
  sampled garbage) and compiles its prefill/step closures ONCE per
  ``(cfg, max_len)`` via the process-wide
  :data:`~repro.core.schedule.EXEC_CACHE` instead of on every call.
  It is the bit-level ORACLE the engine is tested against.
* :class:`DecodeEngine` + :class:`ServeStream` — the production shape:
  the token loop is ONE jitted ``lax.while_loop`` carrying
  ``(cache, logits, lengths, done, step, ...)`` on device, KV lives in
  fixed-size paged slots shared by all sequences, and the stream
  admits/evicts requests *between* waves (continuous batching) while
  prefilling incoming requests on a prefetch thread — the same
  double-buffer discipline as :class:`repro.runtime.jobstream.JobStream`
  uses for map vs shuffle. One host round-trip per WAVE, not per token.

Both paths are SELF-HEALING (DESIGN.md §15). Every request ends in a
terminal status from :data:`STATUSES` — ``ok``, ``expired`` (deadline),
``shed`` (bounded admission queue), ``quarantined`` (non-finite logits)
or ``retried_ok`` (finished after >= 1 wave retry). The engine
snapshots its device wave state into a double-buffered slot at every
wave boundary, so the stream's supervisor can retry a crashed or
timed-out wave from the snapshot with bounded backoff — replay is
bitwise-identical to the fault-free run because the snapshot carries
the token buffer, lens/done/emitted, page tables and the per-request
PRNG chains. A device-side NaN/Inf sentinel
(:func:`repro.models.lm.poisoned_rows`) rides in the jitted wave carry
and quarantines exactly the poisoned slot while its batch siblings
continue undisturbed. All snapshot/restore/evict executables live in
the process-wide EXEC_CACHE, so the whole recovery path retraces
NOTHING after warmup.
"""

from __future__ import annotations

import time
from collections import Counter, deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs import ModelConfig
from repro.core.schedule import EXEC_CACHE
from repro.models import lm

__all__ = ["GenerationResult", "generate", "serve_legacy", "Request",
           "ServeResult", "STATUSES", "PagePool", "DecodeEngine",
           "ServeStream", "ServeReport", "WaveCrashError",
           "WaveTimeoutError", "trace_total", "TRACE_COUNTS"]

#: host spans in the profiler's trace (``serve.*``); about a microsecond
#: each while no profiler is on
_span = jax.profiler.TraceAnnotation

#: terminal request statuses — every submitted request ends in exactly
#: one of these, on both serving paths (DESIGN.md §15)
STATUSES = ("ok", "expired", "shed", "quarantined", "retried_ok")


class WaveCrashError(RuntimeError):
    """A decode wave died before its results could be committed (real
    crash, or injected by the serving chaos layer). The supervisor
    rolls the engine back to the wave-boundary snapshot and retries."""


class WaveTimeoutError(RuntimeError):
    """A decode wave exceeded ``ServeStream.wave_timeout_s``. Treated
    exactly like a crash: its (possibly complete) results are discarded
    and the wave is replayed from the snapshot — replay is bitwise
    equal, so discarding a late wave never changes any token."""


# --------------------------------------------------------------------- #
# compilation accounting
# --------------------------------------------------------------------- #
#: traces per executable-cache key. A bump happens when jax TRACES the
#: wrapped python function — i.e. on every (re)compilation. Steady-state
#: serving (and a second ``generate`` call of the same shape) must not
#: move these counters; tests and the bench recompile gate assert on
#: :func:`trace_total`.
TRACE_COUNTS: Counter = Counter()


def trace_total() -> int:
    """Total number of jit traces paid by the serving entry points."""
    return sum(TRACE_COUNTS.values())


def _counted_jit(key, fn, **jit_kw):
    """``jax.jit(fn)`` that bumps ``TRACE_COUNTS[key]`` at trace time."""

    def traced(*args, **kwargs):
        TRACE_COUNTS[key] += 1
        return fn(*args, **kwargs)

    return jax.jit(traced, **jit_kw)


# --------------------------------------------------------------------- #
# legacy host loop (the oracle)
# --------------------------------------------------------------------- #
@dataclass
class GenerationResult:
    tokens: np.ndarray          # [B, T_out]
    steps: int
    prefill_len: int
    #: host-loop wall time per emitted token (the per-token latency the
    #: serving bench samples p50/p99 from)
    step_times: np.ndarray | None = None


def _legacy_fns(cfg: ModelConfig, max_len: int):
    """Jitted (prefill, decode_step) pair for ``(cfg, max_len)``.

    Hoisted out of :func:`generate` into the process-wide
    :data:`~repro.core.schedule.EXEC_CACHE`: the seed implementation
    built ``jax.jit(lambda ...)`` closures inside the function body, so
    EVERY call retraced and recompiled both.
    """
    key = ("serve_legacy", cfg, max_len)

    def build():
        def prefill_fn(p, b):
            TRACE_COUNTS[key] += 1
            return lm.prefill(cfg, p, b, max_len=max_len)

        def step_fn(p, c, t, i):
            TRACE_COUNTS[key] += 1
            return lm.decode_step(cfg, p, c, t, i)

        return jax.jit(prefill_fn), jax.jit(step_fn)

    return EXEC_CACHE.get(key, build)


def generate(cfg: ModelConfig, params, prompts: np.ndarray, *,
             max_new: int = 32, eos: int | None = None,
             temperature: float = 0.0, seed: int = 0,
             extras: dict | None = None,
             pad: int | None = None) -> GenerationResult:
    """prompts: [B, T_prompt] int32 (right-aligned, no padding support
    needed for the examples). Greedy when temperature == 0.

    Stop handling is deterministic: once a row has emitted ``eos``,
    every later column of that row is ``pad`` (default: the eos id
    itself) — never a sampled token. This fixed behavior is the oracle
    :class:`DecodeEngine` is tested against.
    """
    B, T = prompts.shape
    max_len = T + max_new
    batch = {"tokens": jnp.asarray(prompts)}
    if extras:
        batch.update({k: jnp.asarray(v) for k, v in extras.items()})

    prefill_fn, step_fn = _legacy_fns(cfg, max_len)

    logits, cache = prefill_fn(params, batch)
    key = jax.random.PRNGKey(seed)
    out = [np.asarray(prompts)]
    done = np.zeros(B, bool)
    fill = np.int32(pad if pad is not None else (eos if eos is not None
                                                 else 0))
    times: list[float] = []
    for i in range(max_new):
        t0 = time.perf_counter()
        lg = logits[:, -1, :cfg.vocab]       # drop vocab padding
        if temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, lg / temperature)
        else:
            nxt = jnp.argmax(lg, axis=-1)
        cur = np.asarray(nxt, np.int32)[:, None]
        if eos is not None:
            # finished rows emit the pad/eos id forever (deterministic
            # post-stop tail), never the sampled garbage
            cur = np.where(done[:, None], fill, cur)
            out.append(cur)
            done |= (cur[:, 0] == eos)
            if done.all():
                times.append(time.perf_counter() - t0)
                break
        else:
            out.append(cur)
        logits, cache = step_fn(params, cache, jnp.asarray(cur),
                                jnp.int32(T + i))
        jax.block_until_ready(logits)
        times.append(time.perf_counter() - t0)
    return GenerationResult(tokens=np.concatenate(out, axis=1),
                            steps=len(out) - 1, prefill_len=T,
                            step_times=np.asarray(times))


def serve_legacy(cfg: ModelConfig, params, requests, *,
                 max_queue: int | None = None,
                 shed_policy: str = "newest", clock=None,
                 extras: dict | None = None,
                 model: str = "") -> list:
    """Serve :class:`Request` s through the HOST generate loop with the
    SAME per-request deadline/status accounting as :class:`ServeStream`
    — the enc-dec/frontend configs (and ``--legacy``) get uniform
    :class:`ServeResult` s instead of silently lacking failure fields.

    Sequential FIFO over one model: queue overflow beyond ``max_queue``
    is shed at submission (``shed_policy`` as in the stream), deadlines
    are checked before start and between tokens (an expired request
    keeps its clean prefix), and every request terminates with a status
    from :data:`STATUSES` (``quarantined``/``retried_ok`` never occur —
    the host loop has no shared slots to poison and no wave to retry).
    Tokens are bitwise the :func:`generate` oracle's.
    """
    if shed_policy not in ("newest", "oldest"):
        raise ValueError(f"unknown shed_policy {shed_policy!r}")
    now = clock if clock is not None else time.monotonic
    t_start = now()
    results: list = [None] * len(requests)
    order = deque(enumerate(requests))
    if max_queue is not None:
        while len(order) > max_queue:
            i, req = (order.pop() if shed_policy == "newest"
                      else order.popleft())
            prompt = np.asarray(req.prompt, np.int32)
            results[i] = ServeResult(
                tokens=prompt, prompt_len=prompt.shape[0], emitted=0,
                model=model, index=i, status="shed")
    for i, req in order:
        prompt = np.asarray(req.prompt, np.int32)
        T = prompt.shape[0]
        deadline = (None if req.deadline_s is None
                    else t_start + req.deadline_s)
        if deadline is not None and now() >= deadline:
            results[i] = ServeResult(
                tokens=prompt, prompt_len=T, emitted=0, model=model,
                index=i, status="expired")
            continue
        prefill_fn, step_fn = _legacy_fns(cfg, T + req.max_new)
        batch = {"tokens": jnp.asarray(prompt[None])}
        if extras:
            batch.update({k: jnp.asarray(v) for k, v in extras.items()})
        logits, cache = prefill_fn(params, batch)
        key = jax.random.PRNGKey(req.seed)
        toks: list[int] = []
        status = "ok"
        for t in range(req.max_new):
            if deadline is not None and now() >= deadline:
                status = "expired"      # cancel mid-request, keep prefix
                break
            lg = logits[:, -1, :cfg.vocab]
            if req.temperature > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, lg / req.temperature)
            else:
                nxt = jnp.argmax(lg, axis=-1)
            cur = int(np.asarray(nxt)[0])
            toks.append(cur)
            if req.eos is not None and cur == req.eos:
                break
            if t + 1 < req.max_new:
                logits, cache = step_fn(
                    params, cache, jnp.asarray([[cur]], jnp.int32),
                    jnp.int32(T + t))
        results[i] = ServeResult(
            tokens=np.concatenate([prompt,
                                   np.asarray(toks, np.int32)]),
            prompt_len=T, emitted=len(toks), model=model, index=i,
            status=status)
    return results


# --------------------------------------------------------------------- #
# requests / results
# --------------------------------------------------------------------- #
@dataclass(frozen=True)
class Request:
    """One serving request (a single sequence)."""

    prompt: np.ndarray = field(repr=False)     # [T] int32
    max_new: int = 32
    eos: int | None = None
    temperature: float = 0.0
    seed: int = 0                               # per-request PRNG chain
    pad: int | None = None                      # post-eos fill (def: eos)
    #: wall-clock budget in seconds from submission; None = no deadline.
    #: Checked between waves (engine path) / between tokens (legacy
    #: path): an expired request terminates with status "expired" and
    #: whatever clean tokens it had emitted so far.
    deadline_s: float | None = None

    @property
    def fill(self) -> int:
        if self.pad is not None:
            return self.pad
        return self.eos if self.eos is not None else 0


@dataclass
class ServeResult:
    """Terminated request: ``tokens`` = prompt + generated ids; generated
    cells past the stop point carry the request's pad/eos fill.

    ``status`` is one of :data:`STATUSES` and is UNIFORM across the
    engine and legacy serving paths. Non-``ok`` results still carry
    every clean token emitted before termination (``shed`` requests
    carry none) — a quarantined/expired result's generated prefix is
    bitwise equal to the fault-free run's prefix.
    """

    tokens: np.ndarray
    prompt_len: int
    emitted: int
    model: str = ""
    index: int = -1
    status: str = "ok"
    #: wave retries survived while this request was live on a slot
    retries: int = 0
    #: ``(time.monotonic(), tokens)`` per commit that delivered tokens,
    #: after a first ``(admitted_at, 0)``: ``deliveries[1][0] -
    #: deliveries[0][0]`` is the time to first token; the token counts
    #: sum to ``emitted``. Empty for a request never admitted.
    deliveries: list = field(default_factory=list, repr=False)

    @property
    def generated(self) -> np.ndarray:
        return self.tokens[self.prompt_len:]

    @property
    def ok(self) -> bool:
        return self.status in ("ok", "retried_ok")


# --------------------------------------------------------------------- #
# paged KV slots
# --------------------------------------------------------------------- #
class PagePool:
    """Host-side physical-page allocator for the paged KV cache.

    Page 0 is the reserved TRASH page (finished rows' writes are routed
    there on device); pages ``1..n_pages-1`` are allocatable. Allocation
    is deterministic (lowest free ids first) so engine runs are
    reproducible. The invariant the paged cache relies on — no two live
    slots ever share a physical page, and nobody owns the trash page —
    is checkable at any time via :meth:`check_invariants`.
    """

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the trash page)")
        self.n_pages = n_pages
        self._free = list(range(1, n_pages))
        self._owned: dict[int, list[int]] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, slot: int, n: int) -> list[int] | None:
        """``n`` pages for ``slot``; None when the pool is exhausted
        (the request stays queued until evictions free pages)."""
        if slot in self._owned:
            raise ValueError(f"slot {slot} already owns pages")
        if n > len(self._free):
            return None
        pages, self._free = self._free[:n], self._free[n:]
        self._owned[slot] = pages
        return pages

    def free(self, slot: int) -> None:
        pages = self._owned.pop(slot)
        self._free.extend(pages)
        self._free.sort()

    def check_invariants(self) -> None:
        seen: set[int] = set()
        for slot, pages in self._owned.items():
            for p in pages:
                if p == 0:
                    raise AssertionError(f"slot {slot} owns trash page 0")
                if p in seen:
                    raise AssertionError(
                        f"page {p} aliased by two live slots")
                if not 0 < p < self.n_pages:
                    raise AssertionError(f"page {p} out of range")
                seen.add(p)
        if seen & set(self._free):
            raise AssertionError("page both owned and free")


# --------------------------------------------------------------------- #
# the device-resident decode engine
# --------------------------------------------------------------------- #
class DecodeEngine:
    """Continuous-batching decode engine: paged KV slots + ONE jitted
    ``lax.while_loop`` per wave (DESIGN.md §13).

    ``slots`` sequences decode simultaneously; each may hold up to
    ``pages_per_slot = ceil(max_ctx / page_size)`` pages out of a shared
    pool of ``n_pages`` physical pages (default: enough for every slot
    to max out; pass a smaller pool to get real paging pressure —
    admission then waits for evictions). All per-sequence decode state
    (cache pages, next-token logits, lengths, done flags, PRNG chains,
    emitted-token buffers) lives on device; a wave of up to ``wave_len``
    tokens runs without host contact and only the tiny
    ``done``/``emitted`` vectors sync back.

    Greedy tokens are bit-compatible with the fixed :func:`generate`
    oracle; temperature>0 follows the per-request PRNG chain
    ``PRNGKey(request.seed)`` split once per step — exactly the oracle's
    ``B=1`` chain.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 page_size: int = 8, max_ctx: int = 64,
                 n_pages: int | None = None, max_new_cap: int = 64,
                 name: str = ""):
        if cfg.family == "encdec" or cfg.frontend:
            raise NotImplementedError(
                f"{cfg.name}: enc-dec / frontend models are served by "
                "the legacy generate() path, not DecodeEngine")
        self.cfg, self.params, self.name = cfg, params, name
        self.slots = slots
        self.page_size = page_size
        self.pages_per_slot = -(-max_ctx // page_size)
        self.capacity = self.pages_per_slot * page_size
        self.max_new_cap = max_new_cap
        self.n_pages = (1 + slots * self.pages_per_slot
                        if n_pages is None else n_pages)
        self.pool = PagePool(self.n_pages)
        self._sig = (slots, self.n_pages, page_size, self.pages_per_slot,
                     max_new_cap)
        self._free_slots = list(range(slots))
        self._live: dict[int, dict] = {}
        self._step_prev = 0
        self.st = self._init_state()
        self._wave_fn = self._build_wave()
        # double-buffered wave-boundary snapshots (DESIGN.md §15): the
        # copy lands in the idle slot and only then does the valid
        # index flip, so a crash mid-snapshot still leaves the previous
        # boundary restorable. Cost: 2x the per-engine state memory,
        # nothing on the wave critical path but one async device copy.
        self._snaps: list = [None, None]
        self._snap_i = 0
        self.rollbacks = 0

    # -- device state --------------------------------------------------- #
    def _init_state(self) -> dict:
        S, V = self.slots, self.cfg.vocab_padded
        return {
            "cache": lm.init_paged_cache(self.cfg, S, self.n_pages,
                                         self.page_size,
                                         self.pages_per_slot),
            "logits": jnp.zeros((S, V), jnp.float32),
            "len": jnp.zeros((S,), jnp.int32),
            "done": jnp.ones((S,), bool),
            "emitted": jnp.zeros((S,), jnp.int32),
            "eos": jnp.full((S,), -1, jnp.int32),
            "cap": jnp.zeros((S,), jnp.int32),
            "fill": jnp.zeros((S,), jnp.int32),
            "temp": jnp.zeros((S,), jnp.float32),
            "keys": jnp.zeros((S, 2), jnp.uint32),
            "buf": jnp.zeros((S, self.max_new_cap), jnp.int32),
            "step": jnp.zeros((), jnp.int32),
            # NaN/Inf sentinel carried by the wave body: True marks a
            # slot whose logits went non-finite (-> quarantined)
            "poison": jnp.zeros((S,), bool),
        }

    # -- jitted executables (EXEC_CACHE-keyed, trace-counted) ----------- #
    def _build_wave(self):
        cfg, S, buf_T = self.cfg, self.slots, self.max_new_cap
        vocab = cfg.vocab
        key = ("serve_wave", cfg) + self._sig

        def build():
            def sample_row(k, lg, temp):
                k2, sub = jax.random.split(k)
                greedy = jnp.argmax(lg).astype(jnp.int32)
                z = (lg / jnp.where(temp > 0, temp, 1.0))[None, :]
                samp = jax.random.categorical(sub, z)[0].astype(jnp.int32)
                return k2, jnp.where(temp > 0, samp, greedy)

            def wave(params, st, wave_len):
                TRACE_COUNTS[key] += 1

                def cond(carry):
                    st, i = carry
                    return (i < wave_len) & ~jnp.all(st["done"])

                def body(carry):
                    st, i = carry
                    with jax.named_scope("head"):
                        # 0. poisoned-slot sentinel (DESIGN.md §15): a
                        #    live row whose carried logits went
                        #    non-finite stops HERE — before its garbage
                        #    sample could be emitted — so its buffer
                        #    holds exactly the clean prefix. Rows are
                        #    independent through sampling and decode, so
                        #    siblings are undisturbed.
                        bad = lm.poisoned_rows(st["logits"], vocab) \
                            & ~st["done"]
                        poison = st["poison"] | bad
                        # 1. sample from the carried logits (the
                        #    oracle's order: prefill logits feed the
                        #    first token)
                        keys, nxt = jax.vmap(sample_row)(
                            st["keys"], st["logits"][:, :vocab],
                            st["temp"])
                        # a poisoned row's sample is garbage — feed the
                        #    decode step its pad fill (a valid token id)
                        nxt = jnp.where(bad, st["fill"], nxt)
                        done = st["done"] | bad
                        rows = jnp.arange(S)
                        pos = jnp.minimum(st["emitted"], buf_T - 1)
                        # finished rows re-write their current cell's
                        # value (a no-op) so their tail stays at the pad
                        # fill
                        old = st["buf"][rows, pos]
                        buf = st["buf"].at[rows, pos].set(
                            jnp.where(done, old, nxt))
                        emitted = st["emitted"] + jnp.where(done, 0, 1)
                        just_eos = ((~done) & (st["eos"] >= 0)
                                    & (nxt == st["eos"]))
                        done2 = done | just_eos | (emitted >= st["cap"])
                    # 2. device-side stop handling: finished rows write
                    #    to the trash page (index -1) and freeze length
                    ci = jnp.where(done2, -1, st["len"])
                    logits, cache = lm.decode_step(
                        cfg, params, st["cache"], nxt[:, None], ci)
                    st2 = dict(st, cache=cache, logits=logits[:, 0],
                               keys=keys, buf=buf, emitted=emitted,
                               done=done2, poison=poison,
                               len=st["len"] + jnp.where(done2, 0, 1),
                               step=st["step"] + 1)
                    return st2, i + 1

                st, _ = lax.while_loop(cond, body, (st, jnp.int32(0)))
                return st

            # params (arg 0) are shared across engines — only the state
            # buffers are donated
            return jax.jit(wave, donate_argnums=(1,))

        return EXEC_CACHE.get(key, build)

    def _prefill_fn(self, T: int):
        cfg = self.cfg
        Tp = -(-T // self.page_size) * self.page_size
        key = ("serve_prefill", cfg, T, Tp)

        def build():
            def pf(params, tokens):
                TRACE_COUNTS[key] += 1
                return lm.prefill(cfg, params, {"tokens": tokens},
                                  max_len=Tp)

            return jax.jit(pf)

        return EXEC_CACHE.get(key, build)

    def _admit_fn(self, T: int):
        cfg = self.cfg
        key = ("serve_admit", cfg, T) + self._sig

        def build():
            def admit(st, slot, pages, pcache, logits0, eos, cap, temp,
                      fill, prng):
                TRACE_COUNTS[key] += 1
                cache = lm.admit_prefill(cfg, st["cache"], pcache, pages,
                                         slot)
                return dict(
                    st, cache=cache,
                    logits=st["logits"].at[slot].set(logits0),
                    len=st["len"].at[slot].set(T),
                    done=st["done"].at[slot].set(False),
                    emitted=st["emitted"].at[slot].set(0),
                    eos=st["eos"].at[slot].set(eos),
                    cap=st["cap"].at[slot].set(cap),
                    temp=st["temp"].at[slot].set(temp),
                    fill=st["fill"].at[slot].set(fill),
                    keys=st["keys"].at[slot].set(prng),
                    buf=st["buf"].at[slot].set(fill),
                    poison=st["poison"].at[slot].set(False),
                )

            return jax.jit(admit, donate_argnums=(0,))

        return EXEC_CACHE.get(key, build)

    def _snap_fn(self):
        """Jitted deep copy of the wave state — fresh device buffers,
        so the original survives the wave executable's donation. Used
        both to TAKE a snapshot (copy ``st``) and to RESTORE one (copy
        the snapshot back, keeping it intact for another retry)."""
        key = ("serve_snapshot", self.cfg) + self._sig

        def build():
            def snap(st):
                TRACE_COUNTS[key] += 1
                return jax.tree.map(jnp.copy, st)

            return jax.jit(snap)

        return EXEC_CACHE.get(key, build)

    def _evict_fn(self):
        """Jitted slot freeze: marks one row done so the wave loop
        stops decoding it (its writes route to the trash page)."""
        key = ("serve_evict", self.cfg) + self._sig

        def build():
            def ev(st, slot):
                TRACE_COUNTS[key] += 1
                return dict(st, done=st["done"].at[slot].set(True))

            return jax.jit(ev, donate_argnums=(0,))

        return EXEC_CACHE.get(key, build)

    def _poison_fn(self):
        """Jitted logit corruption of one slot (chaos injection): the
        next wave body's sentinel must flag exactly this row."""
        key = ("serve_poison", self.cfg) + self._sig

        def build():
            def pz(st, slot):
                TRACE_COUNTS[key] += 1
                row = jnp.full_like(st["logits"][slot], jnp.nan)
                return dict(st, logits=st["logits"].at[slot].set(row))

            return jax.jit(pz, donate_argnums=(0,))

        return EXEC_CACHE.get(key, build)

    # -- host-side protocol --------------------------------------------- #
    @property
    def live(self) -> int:
        return len(self._live)

    @property
    def has_free_slot(self) -> bool:
        return bool(self._free_slots)

    def validate(self, req: Request) -> None:
        T = int(np.asarray(req.prompt).shape[0])
        if T + req.max_new > self.capacity:
            raise ValueError(
                f"request needs {T + req.max_new} cache positions > slot "
                f"capacity {self.capacity} (= pages_per_slot * page_size)")
        if req.max_new > self.max_new_cap:
            raise ValueError(f"max_new {req.max_new} > engine "
                             f"max_new_cap {self.max_new_cap}")
        if -(-(T + req.max_new) // self.page_size) > self.n_pages - 1:
            raise ValueError("request needs more pages than the pool has")

    def prefill(self, req: Request) -> dict:
        """Run (jitted) prefill for a request — safe to call from the
        stream's prefetch thread while a wave is in flight."""
        prompt = np.asarray(req.prompt, np.int32)
        T = prompt.shape[0]
        logits, cache = self._prefill_fn(T)(self.params,
                                            jnp.asarray(prompt[None]))
        return {"T": T, "logits": logits[0, 0], "cache": cache}

    def admit(self, req: Request, pre: dict | None = None,
              handle=None) -> int | None:
        """Admit a request into a free slot (between waves). Returns the
        slot id, or None when no slot / not enough free pages."""
        if not self._free_slots:
            return None
        T = pre["T"] if pre else int(np.asarray(req.prompt).shape[0])
        n_total = -(-(T + req.max_new) // self.page_size)
        slot = self._free_slots[0]
        pages = self.pool.alloc(slot, n_total)
        if pages is None:
            return None          # paging pressure: caller keeps it queued
        self._free_slots.pop(0)
        if pre is None:
            pre = self.prefill(req)
        row = np.zeros(self.pages_per_slot, np.int32)
        row[:n_total] = pages
        eos = -1 if req.eos is None else int(req.eos)
        with _span("serve.admit", req=handle):
            self.st = self._admit_fn(T)(
                self.st, jnp.int32(slot), jnp.asarray(row), pre["cache"],
                pre["logits"], jnp.int32(eos), jnp.int32(req.max_new),
                jnp.float32(req.temperature), jnp.int32(req.fill),
                jax.random.PRNGKey(req.seed))
        self._live[slot] = {"handle": handle, "prompt_len": T,
                            "prompt": np.asarray(req.prompt, np.int32),
                            "emitted_prev": 0, "retries": 0,
                            "deliveries": [(time.monotonic(), 0)]}
        return slot

    # -- self-healing protocol (DESIGN.md §15) -------------------------- #
    def snapshot(self) -> None:
        """Copy the device wave state into the idle snapshot slot, then
        flip the valid index (the commit point). Called at every wave
        boundary by :meth:`wave`."""
        nxt = 1 - self._snap_i
        with _span("serve.snapshot"):
            self._snaps[nxt] = self._snap_fn()(self.st)
        self._snap_i = nxt

    def rollback(self) -> None:
        """Restore the device state from the latest snapshot (keeping
        the snapshot intact for further retries). Host-side bookkeeping
        (live slots, page tables, emitted counters) needs no restore:
        it only mutates at wave COMMIT and at admissions, both of which
        happen before the snapshot is taken — a crashed attempt never
        touched it."""
        snap = self._snaps[self._snap_i]
        if snap is None:
            raise WaveCrashError(
                f"engine {self.name!r}: no snapshot to roll back to "
                "(crash before the first wave boundary)")
        with _span("serve.rollback"):
            self.st = self._snap_fn()(snap)
        self.rollbacks += 1

    def mark_retried(self) -> None:
        """Count one survived wave retry on every live request (their
        terminal status becomes ``retried_ok`` instead of ``ok``)."""
        for h in self._live.values():
            h["retries"] += 1

    def poison_slot(self, slot: int) -> None:
        """Chaos injection: corrupt one live slot's carried logits to
        NaN on device. The next wave body's sentinel — not any host
        code — must detect and quarantine it."""
        if slot not in self._live:
            raise ValueError(f"slot {slot} is not live")
        self.st = self._poison_fn()(self.st, jnp.int32(slot))

    def evict(self, slot: int, status: str = "expired"):
        """Evict a LIVE slot between waves (deadline cancellation).
        Freezes the row on device, frees its pages, and returns
        ``(handle, ServeResult)`` carrying the clean tokens emitted so
        far."""
        h = self._live.pop(slot)
        with _span("serve.evict", req=h["handle"]):
            self.st = self._evict_fn()(self.st, jnp.int32(slot))
            e = int(np.asarray(self.st["emitted"])[slot])
            buf = np.asarray(self.st["buf"][slot, :e])
        self._deliver(h, e, time.monotonic())
        self.pool.free(slot)
        self._free_slots.append(slot)
        self._free_slots.sort()
        res = ServeResult(
            tokens=np.concatenate([h["prompt"], buf]),
            prompt_len=h["prompt_len"], emitted=e, model=self.name,
            status=status, retries=h["retries"],
            deliveries=h["deliveries"])
        return h["handle"], res

    def run_wave(self, wave_len: int = 8, *, crash_hook=None) -> None:
        """The DEVICE half of a wave: snapshot, then up to ``wave_len``
        jitted decode steps. NO host bookkeeping moves — that is
        :meth:`commit_wave`'s job, so a supervisor can still discard
        this attempt (crash, timeout) via :meth:`rollback` without
        un-winding any host state.

        The wave-boundary snapshot is taken BEFORE the device wave runs
        (the wave executable donates the state buffers, so the copy is
        the only way back). ``crash_hook(engine)``, when given, fires
        after the device wave is dispatched but before any commit — the
        chaos layer raises :class:`WaveCrashError` there, leaving the
        engine exactly as a real mid-wave crash would: advanced device
        state, untouched host bookkeeping, and a valid snapshot to
        :meth:`rollback` to.
        """
        self.snapshot()
        with _span("serve.dispatch"):
            self.st = self._wave_fn(self.params, self.st,
                                    jnp.int32(wave_len))
        if crash_hook is not None:
            crash_hook(self)
        # honest attempt timing for the supervisor's timeout check: the
        # wave is only "done" when its buffers are
        with _span("serve.block"):
            jax.block_until_ready(self.st["done"])

    def commit_wave(self):
        """The HOST half of a wave: sync the finished set back, evict
        it, settle token accounting. Returns ``(finished,
        tokens_emitted, steps_run)`` where ``finished`` is a list of
        ``(slot, handle, ServeResult)``. Only call after the attempt is
        accepted — a committed wave cannot be rolled back."""
        with _span("serve.sync"):
            done = np.asarray(self.st["done"])
            poison = np.asarray(self.st["poison"])
            emitted = np.asarray(self.st["emitted"])
            step = int(self.st["step"])
            newly = [s for s in list(self._live) if done[s]]
            buf = np.asarray(self.st["buf"]) if newly else None
        t = time.monotonic()
        steps_run, self._step_prev = step - self._step_prev, step
        tokens = 0
        for s, h in self._live.items():
            tokens += self._deliver(h, int(emitted[s]), t)
        finished = []
        if newly:
            for s in newly:
                h = self._live.pop(s)
                self.pool.free(s)
                self._free_slots.append(s)
                self._free_slots.sort()
                e = int(emitted[s])
                status = ("quarantined" if poison[s]
                          else "retried_ok" if h["retries"] else "ok")
                res = ServeResult(
                    tokens=np.concatenate([h["prompt"], buf[s, :e]]),
                    prompt_len=h["prompt_len"], emitted=e,
                    model=self.name, status=status,
                    retries=h["retries"], deliveries=h["deliveries"])
                finished.append((s, h["handle"], res))
        return finished, tokens, steps_run

    @staticmethod
    def _deliver(h: dict, emitted: int, t: float) -> int:
        """Settle a live request's emitted count at time ``t``: record
        the tokens it gained as one delivery; returns how many."""
        m = emitted - h["emitted_prev"]
        if m:
            h["deliveries"].append((t, m))
            h["emitted_prev"] = emitted
        return m

    def wave(self, wave_len: int = 8, *, crash_hook=None):
        """One unsupervised wave: :meth:`run_wave` + :meth:`commit_wave`
        back to back (the no-faults fast path)."""
        self.run_wave(wave_len, crash_hook=crash_hook)
        return self.commit_wave()


# --------------------------------------------------------------------- #
# the continuous-batching front door
# --------------------------------------------------------------------- #
@dataclass
class ServeReport:
    """What the last :meth:`ServeStream.run` did."""

    requests: int
    waves: int
    admitted: int
    #: mean fraction of batch slots occupied over executed decode steps
    occupancy: float
    #: per-wave samples: (model, wall_s, steps, tokens, live_slots)
    wave_stats: list = field(default_factory=list, repr=False)
    #: jit traces paid during the run (0 after warmup — the
    #: zero-recompilation admission contract; the RECOVERY path is held
    #: to the same bar)
    traces: int = 0
    #: wave retries paid by the supervisor (crashes + timeouts)
    retries: int = 0
    #: terminal-status histogram over this run's requests
    status_counts: dict = field(default_factory=dict)
    #: wall seconds spent on crashed/timed-out wave attempts + rollbacks
    recovery_s: float = 0.0


def _prefill(eng: DecodeEngine, req: Request, idx: int) -> dict:
    with _span("serve.prefill", req=idx):
        return eng.prefill(req)


class ServeStream:
    """Multi-tenant continuous-batching scheduler over
    :class:`DecodeEngine` s — the serving sibling of
    :class:`repro.runtime.jobstream.JobStream`'s wave batcher.

    Requests are FIFO per model. Each scheduler iteration (1) tops up
    the prefill prefetch lane, (2) runs one decode WAVE per engine with
    live work — while the wave occupies the device, the prefetch thread
    drives prefill of queued requests (the JobStream double-buffer
    discipline) — and (3) evicts finished sequences and admits prefilled
    ones into the freed slots. Jitted executables come from the
    process-wide EXEC_CACHE, so steady-state admission pays ZERO new
    compilations.

    Self-healing policy knobs (DESIGN.md §15):

    ``max_queue``        bounds the per-model admission queue; overflow
                         is load-shed at submission with status
                         ``shed`` (``shed_policy`` picks the victim:
                         ``"newest"`` rejects the incoming tail,
                         ``"oldest"`` sheds the stalest queued work).
    ``wave_timeout_s``   a wave observed slower than this is treated as
                         crashed: discarded and replayed from the
                         snapshot (replay is bitwise, so a late wave
                         never changes a token).
    ``max_retries``      attempts per wave before the supervisor gives
                         up and re-raises; backoff between attempts is
                         ``retry_backoff_s * 2**(attempt-1)``.
    ``chaos``            optional fault-injection hook (duck-typed; see
                         tests/chaos.py ``ServeChaosController``):
                         ``on_wave_start(model, wave, engine)`` before
                         each attempt, ``on_wave_crash(model, wave,
                         engine)`` between device wave and commit (may
                         raise :class:`WaveCrashError`), and
                         ``on_wave_done(model, wave, engine, wall_s)``
                         returning the (possibly inflated) wall time.
                         When it provides ``now()``, deadlines run on
                         that virtual clock — fully deterministic
                         replay, no real clocks.
    """

    def __init__(self, engines, *, wave_len: int = 8, prefetch: int = 2,
                 pipeline: bool = True, max_queue: int | None = None,
                 shed_policy: str = "newest",
                 wave_timeout_s: float | None = None,
                 max_retries: int = 2, retry_backoff_s: float = 0.0,
                 chaos=None, clock=None):
        if isinstance(engines, DecodeEngine):
            engines = {"": engines}
        if shed_policy not in ("newest", "oldest"):
            raise ValueError(f"unknown shed_policy {shed_policy!r}")
        self.engines: dict[str, DecodeEngine] = dict(engines)
        self.wave_len = wave_len
        self.prefetch = max(1, prefetch)
        self.pipeline = pipeline
        self.max_queue = max_queue
        self.shed_policy = shed_policy
        self.wave_timeout_s = wave_timeout_s
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.chaos = chaos
        self._now = (clock if clock is not None
                     else getattr(chaos, "now", None) or time.monotonic)
        self.last_report: ServeReport | None = None

    # -- supervised wave (retry from the wave-boundary snapshot) -------- #
    def _supervised_wave(self, name: str, eng: DecodeEngine, wave: int):
        """One committed wave, surviving up to ``max_retries`` crashed
        or timed-out attempts. Every retry restores the snapshot and
        re-runs the SAME cached executables — zero retraces, bitwise
        replay. Returns ``(finished, tokens, steps, wall_s, retries,
        recovery_s)``."""
        attempt, lost_s = 0, 0.0
        while True:
            t0 = time.perf_counter()
            try:
                if self.chaos is not None:
                    self.chaos.on_wave_start(name, wave, eng)
                hook = None
                if self.chaos is not None:
                    hook = (lambda e: self.chaos.on_wave_crash(
                        name, wave, e))
                with _span("serve.wave", wave=wave, attempt=attempt):
                    eng.run_wave(self.wave_len, crash_hook=hook)
                dt = time.perf_counter() - t0
                if self.chaos is not None:
                    dt = self.chaos.on_wave_done(name, wave, eng, dt)
                # accept/reject BEFORE the host commit: a rejected
                # attempt must leave no trace for rollback to unwind
                if (self.wave_timeout_s is not None
                        and dt > self.wave_timeout_s):
                    raise WaveTimeoutError(
                        f"{name!r} wave {wave}: {dt:.3f}s > "
                        f"wave_timeout_s={self.wave_timeout_s}")
                with _span("serve.commit", wave=wave):
                    fin, toks, steps = eng.commit_wave()
                return fin, toks, steps, dt, attempt, lost_s
            except (WaveCrashError, WaveTimeoutError):
                lost_s += time.perf_counter() - t0
                attempt += 1
                if attempt > self.max_retries:
                    raise
                t1 = time.perf_counter()
                eng.rollback()
                eng.mark_retried()
                lost_s += time.perf_counter() - t1
                if self.retry_backoff_s:
                    time.sleep(self.retry_backoff_s
                               * 2 ** (attempt - 1))

    def run(self, requests: Sequence) -> list[ServeResult]:
        """``requests``: a sequence of :class:`Request` (single-engine
        streams) or ``(model_name, Request)`` pairs. Returns results in
        submission order; every result carries a terminal ``status``
        from :data:`STATUSES`."""
        jobs: list[tuple[str, Request]] = []
        for r in requests:
            name, req = r if isinstance(r, tuple) else ("", r)
            if name not in self.engines:
                raise KeyError(f"no engine named {name!r}")
            self.engines[name].validate(req)
            jobs.append((name, req))
        results: list[ServeResult | None] = [None] * len(jobs)
        t_start = self._now()
        deadline_at = [None if req.deadline_s is None
                       else t_start + req.deadline_s
                       for _, req in jobs]

        def terminal(idx: int, status: str) -> None:
            prompt = np.asarray(jobs[idx][1].prompt, np.int32)
            results[idx] = ServeResult(
                tokens=prompt, prompt_len=prompt.shape[0], emitted=0,
                model=jobs[idx][0], index=idx, status=status)

        queues = {n: deque() for n in self.engines}
        for i, (n, req) in enumerate(jobs):
            queues[n].append((i, req))
        # bounded admission: shed queue overflow NOW, at submission —
        # an explicit early "no" beats a deadline miss later
        if self.max_queue is not None:
            for n, q in queues.items():
                while len(q) > self.max_queue:
                    i, _ = (q.pop() if self.shed_policy == "newest"
                            else q.popleft())
                    terminal(i, "shed")
        pending = {n: deque() for n in self.engines}
        t_traces = trace_total()
        stats: list = []
        waves = admitted = retries = 0
        recovery_s = 0.0
        pool = ThreadPoolExecutor(max_workers=1) if self.pipeline else None
        try:
            while any(r is None for r in results):
                progress = False
                now = self._now()
                for name, eng in self.engines.items():
                    q, pend = queues[name], pending[name]
                    # 0. deadline sweep (between waves): expire queued,
                    #    prefetched and LIVE requests past their budget
                    with _span("serve.sweep"):
                        for lane in (q, pend):
                            for item in [it for it in lane
                                         if deadline_at[it[0]] is not None
                                         and now >= deadline_at[it[0]]]:
                                lane.remove(item)
                                terminal(item[0], "expired")
                                progress = True
                        for slot in [s for s, h in list(eng._live.items())
                                     if deadline_at[h["handle"]] is not None
                                     and now >= deadline_at[h["handle"]]]:
                            handle, res = eng.evict(slot, "expired")
                            res.model, res.index = name, handle
                            results[handle] = res
                            progress = True
                    # 1. top up the prefill prefetch lane
                    while q and len(pend) < self.prefetch:
                        idx, req = q.popleft()
                        if pool is not None:
                            fut = pool.submit(_prefill, eng, req, idx)
                        else:
                            fut = None
                        pend.append((idx, req, fut))
                        progress = True
                    # 2. decode wave (prefetch thread prefills meanwhile)
                    if eng.live:
                        fin, toks, steps, dt, att, lost = \
                            self._supervised_wave(name, eng, waves)
                        retries += att
                        recovery_s += lost
                        stats.append((name, dt, steps, toks, eng.live
                                      + len(fin)))
                        waves += 1
                        progress = True
                        for _slot, handle, res in fin:
                            res.model, res.index = name, handle
                            results[handle] = res
                    # 3. admit prefilled requests into freed slots
                    while pend and eng.has_free_slot:
                        idx, req, fut = pend[0]
                        if fut is None:
                            pre = _prefill(eng, req, idx)
                        else:
                            with _span("serve.prefill_wait", req=idx):
                                pre = fut.result()
                        slot = eng.admit(req, pre, handle=idx)
                        if slot is None:
                            break                # pool pressure: wait
                        pend.popleft()
                        admitted += 1
                        progress = True
                if not progress:
                    raise RuntimeError(
                        "serve stream stalled (no admission possible and "
                        "no live work) — request larger than pool?")
        finally:
            if pool is not None:
                pool.shutdown(wait=True)
        slot_steps = sum(s[2] * s[4] for s in stats)
        cap_steps = sum(s[2] * self.engines[s[0]].slots for s in stats)
        counts = Counter(r.status for r in results)  # type: ignore
        self.last_report = ServeReport(
            requests=len(jobs), waves=waves, admitted=admitted,
            occupancy=(slot_steps / cap_steps) if cap_steps else 0.0,
            wave_stats=stats, traces=trace_total() - t_traces,
            retries=retries,
            status_counts=dict(counts), recovery_s=recovery_s)
        return results  # type: ignore[return-value]
