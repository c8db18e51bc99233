"""Pre-traced batched step functions shared by both serving engines.

One :class:`Stepper` owns one jitted program per kind, each over the
physically paged KV pool and its block table, plus the reset of new
tenants' per-row state:

* ``decode`` — ONE decode iteration over a whole slot table: every row
  advances from its own ``cache_len`` with an ``active`` validity mask,
  greedy sampling fused in-trace, so requests join and leave between
  iterations without retracing or re-dispatching per request.
* ``prefill_chunk`` — a fixed-width chunk of ``prefill_chunk`` tokens
  per row.  A model whose layers are all attention runs the chunk as
  one ``(B, C)`` block through the layer scan: every weight is read
  once and every layer's block table gathered once for the chunk's C
  tokens, which attend causally within the chunk and over the table.
  A model with an SSM layer (whose step consumes one token) runs an
  in-trace ``lax.scan`` of C one-token steps instead.  Per-row
  ``n_valid`` masks ragged prompt tails (and rows that are not
  prefilling at all), so every prompt length — full chunks,
  remainders, idle rows — compiles exactly one trace per batch shape.
  Only the logits at each row's *last* valid position are computed (or
  captured in-carry) and argmax'd, yielding the first generated token
  without materializing per-position logits.
* ``megastep`` — N fused decode iterations as ONE dispatch: an in-trace
  ``lax.scan`` whose carry is (caches, last sampled token, per-row
  ``cache_len``, ``active`` mask, step budget).  Greedy sampling, EOS
  checks and max-token countdown run on device
  (:func:`~repro.runtime.sampling.megastep_advance`), so finished rows
  self-deactivate mid-scan and stop writing their caches; rows still
  holding prompt tokens force-feed them from a host-built ``forced``
  column instead of the sampled carry.  The engine pre-reserves every
  block the scan could write before launching, so the scan never
  allocates (see ``ContinuousEngine._plan_megastep``).  Each distinct N
  is a distinct trace (``megastep_sizes``); a given N never retraces.

Every step function additionally returns an in-trace NaN **watchdog**
flag per row (:func:`~repro.runtime.sampling.logits_watchdog`) — fused
into the dispatch, so a poisoned accelerator result is detected with
zero extra dispatches.  Fault injection uses separately-jitted
*poisoned* twins (built lazily, counted by ``poisoned_traces``): clean
executables never contain injection code.

Trace counters are incremented inside the traced Python bodies (which
run only at trace time), so ``chunk_traces`` / ``decode_traces`` /
``megastep_traces`` observe XLA retraces directly; ``dispatches``
counts calls.

The programs keep fixed names (:data:`DECODE_PROGRAM`,
:data:`MEGASTEP_PROGRAM`, :data:`CHUNK_PROGRAM`, :data:`RESET_PROGRAM`),
by which a profiler trace's reduction finds them, and the sampling tail
of every step runs under the ``sample`` named scope (the model's parts
carry their own).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..models.blocks import block_pattern
from .sampling import (greedy_serving, logits_watchdog, megastep_advance,
                       poison_logits, select_tokens)
from .telemetry import MetricsRegistry

#: The served step programs' names, as XLA and the profiler show them
#: (``jax.jit`` names a program ``jit_<function>``): the single decode
#: step, the decode megastep's scan, the chunked prefill and the reset
#: of new tenants' per-row state.  Profiler reductions find the
#: programs by these names.
DECODE_PROGRAM = "jit_step"
MEGASTEP_PROGRAM = "jit_run"
CHUNK_PROGRAM = "jit_run_chunk"
RESET_PROGRAM = "jit_reset"


def _program(fn, name: str, **jit_kw):
    """``jax.jit`` of ``fn`` as the program named ``name``."""
    fn.__name__ = name.removeprefix("jit_")
    return jax.jit(fn, **jit_kw)


def _device(x, dtype):
    """Host array -> device array, always copying: CPU-backend
    ``jnp.asarray`` aliases aligned numpy buffers zero-copy, so an engine
    that mutates its slot-table arrays in place (``slot_len += ...``)
    would race the still-in-flight async dispatch reading them."""
    return jnp.array(np.asarray(x), dtype=dtype, copy=True)


class Stepper:
    """Batched, validity-masked decode/prefill dispatches for one model.

    Every step function takes a ``(B, blocks_per_seq)`` block table
    routing every attention layer's physical block pool (see
    ``models.attention.init_paged_kv_cache``).  The table is a traced
    argument — its *values* change every iteration as blocks are
    allocated, shared and freed, with zero retraces.
    """

    def __init__(self, api):
        self.api = api
        self.cfg = api.cfg
        # a Stepper is SHARED across engines (trace reuse), so it owns
        # its own registry rather than borrowing one engine's; the old
        # attribute names survive as the property façade below and
        # engine.stats() merges trace_stats() into its snapshot
        m = MetricsRegistry()
        self.metrics = m
        self._m_chunk_traces = m.counter("stepper.chunk_traces")
        self._m_decode_traces = m.counter("stepper.decode_traces")
        self._m_megastep_traces = m.counter("stepper.megastep_traces")
        # fault-injection twins trace separately (chaos-only): counted
        # apart so the clean counters' no-retrace assertions stay exact
        self._m_poisoned_traces = m.counter("stepper.poisoned_traces")
        self._m_dispatches = m.counter("stepper.dispatches")
        # chunk dispatches that ran the (B, C) block program
        self._m_block_chunks = m.counter("stepper.block_chunks")
        #: the chunk runs as one (B, C) block through the layer scan —
        #: a decoder whose every layer is attention; an SSM step, and
        #: the encoder-decoder's decode step, consume one token
        self.block_chunk = not self.cfg.is_encoder_decoder and all(
            mixer == "attn" for mixer, _ in block_pattern(self.cfg))
        # distinct megastep lengths traced: an N re-appearing would mean
        # a RE-trace (tests assert counter == set size, i.e. one trace
        # per distinct scan length)
        self.megastep_sizes: "set[int]" = set()
        self._decode_paged = _program(self._make_decode(), DECODE_PROGRAM)
        self._chunk_paged = _program(self._make_chunk(), CHUNK_PROGRAM)
        self._mega_paged = _program(self._make_megastep(), MEGASTEP_PROGRAM)
        # the reset writes the caches in place: its input is the
        # engine's only reference, and a copy of every row's state would
        # sit beside the chunk's and the megastep's own copies
        self._reset = _program(self._make_reset(), RESET_PROGRAM,
                               donate_argnums=0)
        # poisoned twins — identical math plus an in-trace NaN injection
        # (sampling.poison_logits) — are built lazily on the first
        # poisoned dispatch: a clean run never compiles injection code
        self._poison_jits: "dict[str, object]" = {}

    # -- metric façade (legacy attribute names) -----------------------------

    @property
    def chunk_traces(self) -> int:
        return self._m_chunk_traces.value

    @property
    def decode_traces(self) -> int:
        return self._m_decode_traces.value

    @property
    def megastep_traces(self) -> int:
        return self._m_megastep_traces.value

    @property
    def poisoned_traces(self) -> int:
        return self._m_poisoned_traces.value

    @property
    def dispatches(self) -> int:
        return self._m_dispatches.value

    def trace_stats(self) -> dict:
        """Counter snapshot + traced megastep lengths — merged into
        ``engine.stats()`` so one snapshot covers the shared stepper."""
        stats = dict(self.metrics.snapshot()["counters"])
        stats["megastep_sizes"] = sorted(self.megastep_sizes)
        return stats

    def _poisoned(self, kind: str):
        fn = self._poison_jits.get(kind)
        if fn is None:
            maker, name = {"decode": (self._make_decode, DECODE_PROGRAM),
                           "mega": (self._make_megastep,
                                    MEGASTEP_PROGRAM)}[kind]
            fn = _program(maker(poisoned=True), name)
            self._poison_jits[kind] = fn
        return fn

    # -- decode -------------------------------------------------------------

    def _make_decode(self, poisoned: bool = False):
        decode = self.api.decode_fn

        def step(params, caches, toks, lens, active, tables, poison=None):
            if poisoned:                     # trace-time side effects
                self._m_poisoned_traces.inc()
            else:
                self._m_decode_traces.inc()
            batch = {"tokens": toks[:, None], "cache_len": lens,
                     "active": active, "block_tables": tables}
            logits, caches = decode(params, caches, batch)
            with jax.named_scope("sample"):
                if poisoned:
                    logits = poison_logits(logits, poison)
                bad = logits_watchdog(logits, active)
                return select_tokens(logits, active, toks), bad, caches

        return step

    def decode(self, params, caches, toks, lens, active, block_tables,
               poison=None):
        """toks/lens/active (B,), block_tables (B, blocks_per_seq) ->
        (next_tok (B,), bad (B,), caches).  ``bad`` flags active rows
        whose logits came back non-finite (the in-dispatch watchdog —
        :func:`~repro.runtime.sampling.logits_watchdog`).  ``poison``
        (B,) bool routes to the lazily-built poisoned twin that NaNs
        those rows' logits in-trace (fault injection — never compiled on
        clean runs)."""
        self._m_dispatches.inc()
        args = (params, caches, _device(toks, jnp.int32),
                _device(lens, jnp.int32), _device(active, bool),
                _device(block_tables, jnp.int32))
        if poison is not None:
            return self._poisoned("decode")(*args, _device(poison, bool))
        return self._decode_paged(*args)

    # -- chunked prefill ----------------------------------------------------

    def _make_chunk(self):
        decode = self.api.decode_fn

        def run_block(params, caches, toks, lens, n_valid, tables):
            self._m_chunk_traces.inc()       # trace-time side effect
            C = toks.shape[1]
            batch = {"tokens": toks, "cache_len": lens,
                     "active": jnp.arange(C, dtype=jnp.int32)
                     < n_valid[:, None],
                     "logits_at": jnp.maximum(n_valid - 1, 0),
                     "block_tables": tables}
            logits, caches = decode(params, caches, batch)
            with jax.named_scope("sample"):
                started = n_valid > 0
                first = jnp.where(started, greedy_serving(logits), 0)
                bad = logits_watchdog(logits, started)
            return caches, lens + n_valid, first, bad

        if self.block_chunk:
            return run_block

        def run_chunk(params, caches, toks, lens, n_valid, tables):
            self._m_chunk_traces.inc()       # trace-time side effect
            B, C = toks.shape

            def step(carry, x):
                caches, lens, first, bad = carry
                tok_col, i = x
                active = i < n_valid
                batch = {"tokens": tok_col[:, None], "cache_len": lens,
                         "active": active, "block_tables": tables}
                logits, caches = decode(params, caches, batch)
                with jax.named_scope("sample"):
                    first = jnp.where(i == n_valid - 1,
                                      greedy_serving(logits), first)
                    bad = bad | logits_watchdog(logits, active)
                lens = lens + active.astype(jnp.int32)
                return (caches, lens, first, bad), None

            first0 = jnp.zeros((B,), jnp.int32)
            bad0 = jnp.zeros((B,), bool)
            (caches, lens, first, bad), _ = jax.lax.scan(
                step, (caches, lens, first0, bad0),
                (jnp.swapaxes(toks, 0, 1), jnp.arange(C, dtype=jnp.int32)))
            return caches, lens, first, bad

        return run_chunk

    def prefill_chunk(self, params, caches, toks, lens, n_valid,
                      block_tables):
        """toks (B, C); lens/n_valid (B,); block_tables (B,
        blocks_per_seq).  Consumes ``n_valid[b]`` prompt tokens for row b
        starting at its ``lens[b]`` cache position.  Returns (caches, new
        lens, first-token per row — meaningful only for rows whose prompt
        completed inside this chunk, watchdog flag per row OR-ed over the
        chunk's tokens)."""
        self._m_dispatches.inc()
        if self.block_chunk:
            self._m_block_chunks.inc()
        return self._chunk_paged(params, caches, _device(toks, jnp.int32),
                                 _device(lens, jnp.int32),
                                 _device(n_valid, jnp.int32),
                                 _device(block_tables, jnp.int32))

    # -- decode megastep ----------------------------------------------------

    def _make_megastep(self, poisoned: bool = False):
        decode = self.api.decode_fn

        def run(params, caches, toks, lens, active, budget, forced,
                n_forced, eos_ids, tables, poison=None):
            if poisoned:                     # trace-time side effects
                self._m_poisoned_traces.inc()
            else:
                self._m_megastep_traces.inc()
                self.megastep_sizes.add(forced.shape[1])
            N = forced.shape[1]

            def body(carry, xs):
                caches, last, lens, active, budget, bad = carry
                f_col, s = xs
                # rows still consuming prompt (or a resumed request's
                # re-fed last token) take the forced column; everyone
                # else feeds back the sampled carry
                tok_in = jnp.where(s < n_forced, f_col, last)
                batch = {"tokens": tok_in[:, None], "cache_len": lens,
                         "active": active, "block_tables": tables}
                logits, caches = decode(params, caches, batch)
                with jax.named_scope("sample"):
                    if poisoned:
                        # the fault fires at the megastep's FIRST fused
                        # iteration — the engine iteration it was keyed
                        # to
                        logits = poison_logits(logits, poison & (s == 0))
                    bad = bad | logits_watchdog(logits, active)
                    nxt, nactive, budget = megastep_advance(
                        logits, last, active, budget, n_forced, eos_ids,
                        s)
                lens = lens + active.astype(jnp.int32)
                # emit the pre-update mask: which rows EXECUTED this
                # step (wrote their cache and, on gen steps, a token)
                return (caches, nxt, lens, nactive, budget, bad), \
                    (nxt, active)

            bad0 = jnp.zeros_like(active)
            (caches, _, _, _, _, bad), (toks_out, act_out) = jax.lax.scan(
                body, (caches, toks, lens, active, budget, bad0),
                (jnp.swapaxes(forced, 0, 1),
                 jnp.arange(N, dtype=jnp.int32)))
            return toks_out, act_out, bad, caches

        return run

    def megastep(self, params, caches, toks, lens, active, budget,
                 forced, n_forced, eos_ids, block_tables, poison=None):
        """N fused decode iterations, ONE dispatch, ONE host sync.

        toks/lens/active/budget/n_forced/eos_ids (B,); forced (B, N)
        prompt tokens to force-feed (row b uses column s while
        ``s < n_forced[b]``); block_tables (B, blocks_per_seq).  Returns
        ``(toks_out (N, B), act_out (N, B), bad (B,), new caches)`` —
        ``act_out[s]`` is the mask of rows that executed scan step
        ``s``; the token stream of row b is ``toks_out[n_forced[b]-1 :
        steps_taken, b]``; ``bad`` is the in-carry NaN watchdog, OR-ed
        over every executed step.  The caller must have reserved cache
        blocks for every position the scan can write: the scan itself
        never allocates.  ``poison`` (B,) bool routes to the lazily-built
        poisoned twin (fault injection at scan step 0; never compiled on
        clean runs).
        """
        self._m_dispatches.inc()
        args = (params, caches, _device(toks, jnp.int32),
                _device(lens, jnp.int32), _device(active, bool),
                _device(budget, jnp.int32), _device(forced, jnp.int32),
                _device(n_forced, jnp.int32), _device(eos_ids, jnp.int32),
                _device(block_tables, jnp.int32))
        if poison is not None:
            return self._poisoned("mega")(*args, _device(poison, bool))
        return self._mega_paged(*args)

    # -- slot reset ---------------------------------------------------------

    def _make_reset(self):
        def reset(caches, fresh):
            def clear(cache, batch_axis):
                out = {}
                for name, a in cache.items():
                    if name in ("k_pool", "v_pool"):
                        # physical block pools have no batch axis and
                        # need no reset: every position a new tenant can
                        # attend to (t <= cache_len) is freshly written
                        # before it is read, and everything else is
                        # masked to an exact zero contribution
                        out[name] = a
                        continue
                    shape = [1] * a.ndim
                    shape[batch_axis] = fresh.shape[0]
                    out[name] = jnp.where(fresh.reshape(shape),
                                          jnp.zeros_like(a), a)
                return out

            return {"prefix": [clear(c, 0) for c in caches["prefix"]],
                    "period": [clear(c, 1) for c in caches["period"]]}

        return reset

    def reset_rows(self, caches, fresh):
        """Zero every cache entry of rows with ``fresh[b]`` True — a new
        tenant must see exactly the state `init_paged_caches` would give it
        (SSM state / conv windows are carried outside the masked KV
        region, so stale tenants would otherwise leak through).
        ``caches`` is donated: the caller keeps only what it returns."""
        self._m_dispatches.inc()
        return self._reset(caches, _device(fresh, bool))
