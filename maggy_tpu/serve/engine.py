"""Continuous-batching decode engine: one compiled step, slot-based KV cache.

The engine owns a fixed-``B`` decode cache (``init_cache`` rows are *slots*)
and exactly three compiled programs:

* **prefill** — runs one request's prompt (padded to a power-of-two bucket,
  so compile count is O(log max_seq_len), not O(distinct lengths)) through a
  fresh single-row cache and samples the first token from the last valid
  logit. This is the request's TTFT token.
* **admit** — copies that prefilled row into a free slot of the batch cache
  and sets the slot's per-row write index to the TRUE prompt length (the
  pad's garbage K/V sit above the index and are masked by the per-row
  ``written`` bound until decode overwrites them, one slot per step).
* **decode step** — decodes ONE token for every slot under an active mask.
  Every input that varies as requests churn (tokens, positions, mask,
  sampling params, PRNG key rows) is a same-shape array, so the step
  compiles exactly once for the life of the engine — the XLA-friendly
  analogue of vLLM-style continuous batching. The ``serve.decode_retraces``
  gauge and the ``compile.<program>`` series of the recompile sentinel prove it.

Per-request sampling keys: each request carries a base key derived from its
seed; the key for generated-token ``i`` is ``fold_in(base, i)``, so a
request's output depends only on (params, prompt, seed) — never on which
slot it landed in or what else shared the batch.

The per-row cache index (models/transformer.py ``_cached_attention``) is
what makes this work: slots sit at different sequence positions inside one
compiled program.

**Prefix-KV reuse (default; docs/fleet.md):** real traffic shares long
prompt prefixes (system prompts are identical across most requests). When a
new prompt shares a prefix of at least ``prefix_min`` tokens with a
*resident* slot's prompt (:class:`maggy_tpu.serve.prefix.PrefixIndex`), the
engine admits it with one compiled admit-from-prefix program: the source
row's already-computed KV rows ``[0, L)`` are copied device-side into a
fresh row (exact — for a shared prefix every layer input, and therefore
every cached K/V projection, is identical), only the suffix is prefilled
(positions ``L..plen``), and the row is written into the free slot with the
usual per-row index pin. Outputs are byte-identical to a full prefill;
``prefix_hits`` / ``prefix_tokens_saved`` counters prove the saved work.

**Paged KV cache (default; docs/serving.md "Paged KV cache"):** with
``paged=True`` the batch cache is a flat pool of fixed-size pages plus a
per-slot page-table row inside the ONE compiled decode program
(``models/transformer.py::_paged_cached_attention``), and a host-side
:class:`~maggy_tpu.serve.paging.BlockAllocator` owns the physical pages. A
request holds ``ceil(tokens/page_size)`` pages instead of a full
``max_seq_len`` row, so slot count decouples from HBM; prefix reuse becomes
*aliasing* ref-counted pages (zero KV copies for the shared full pages —
only the partial boundary page is copied, through the same one-program
admit) and eviction/preemption is a host-side page-list edit. Pages are
copy-on-write by construction: decode only ever writes past ``plen`` into
privately-owned tail pages, so a shared page is never written in place.
``paged=False`` (or ``MAGGY_TPU_SERVE_PAGED=0``) keeps the dense
row-per-slot path — outputs are byte-identical either way.

**Async decode (default; docs/performance.md):** ``step()`` dispatches
decode step ``i+1`` BEFORE host-reading step ``i``'s sampled tokens.
Continuing slots take their input token straight from the in-flight device
output (``jnp.where(use_prev, prev_sampled, host_tokens)`` inside the jit),
so the device→host→device round-trip per token disappears; the host drains
step ``i`` (``serve.drain_ms`` gauge) while step ``i+1`` computes. Token
streams are byte-identical to the synchronous path — the tokens fed forward
are the same sampled values, positions/keys advance identically, and the
one extra post-finish step a slot decodes before the host learns it
finished is discarded at drain (slot/request identity is checked). Pass
``async_decode=False`` (or ``MAGGY_TPU_SERVE_ASYNC=0``) for the strict
synchronous path.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from maggy_tpu import telemetry
from maggy_tpu.exceptions import BadArgumentsError
from maggy_tpu.models.generate import init_cache, prefill
from maggy_tpu.telemetry import memtrack
from maggy_tpu.serve.paging import BlockAllocator, OutOfPagesError, PageTable
from maggy_tpu.serve.prefix import PrefixIndex
from maggy_tpu.serve.request import Request
from maggy_tpu.serve.slots import SlotManager, SlotOccupiedError
from maggy_tpu.serve.tier import HostPagePool, TieringPolicy

# fixed-size top-k filter: per-request top_k rides in as an array, the kth
# threshold is read from a static top-TOPK_CAP sort, keeping the decode step
# shape-stable for any requested k in [1, TOPK_CAP]
TOPK_CAP = 64

# smallest prefill bucket; prompts shorter than this share one compile
MIN_PREFILL_BUCKET = 8

# default KV page size (tokens) for the paged cache; must divide max_seq_len
DEFAULT_PAGE_SIZE = 16
# how long after the last compile of any of its programs the engine counts as
# warming up: the longest window of the latency alerts (telemetry/alerts.py),
# so every sample a compile stretched has aged out when they are evaluated
WARMUP_QUIET_S = 30.0


def _sample_one(logits, temp, top_k, key):
    """Sample one token from one row's logits with dynamic temperature and
    (capped) top-k. ``temp <= 0`` is exact greedy — argmax, no RNG consumed —
    so greedy engine output can be compared token-for-token against
    :func:`maggy_tpu.models.generate.generate_cached`."""
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits).astype(jnp.int32)
        cap = min(TOPK_CAP, logits.shape[-1])
        top_vals = jax.lax.top_k(logits, cap)[0]  # sorted desc
        kth = top_vals[jnp.clip(top_k - 1, 0, cap - 1)]
        filtered = jnp.where((top_k > 0) & (logits < kth), -jnp.inf, logits)
        scaled = filtered / jnp.maximum(temp, 1e-6)
        sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
        return jnp.where(temp > 0.0, sampled, greedy)


def _base_key_data(seed: int) -> np.ndarray:
    """uint32 key data for a request's base PRNG key (host-side; raw key
    data rather than typed keys so rows stack/update like any array)."""
    return np.asarray(jax.random.key_data(jax.random.key(seed)), np.uint32)


@dataclasses.dataclass
class StepOutput:
    """One decode step's per-slot results (host-side)."""

    tokens: Dict[int, int]  # slot -> sampled token (active slots only)


class Engine:
    """Slot-based continuous-batching engine over a ``DecoderConfig`` model.

    Synchronous and single-threaded by design: the scheduler serializes all
    calls. ``params`` are the trained (non-decode) params, exactly what
    ``generate_cached`` takes.
    """

    def __init__(
        self,
        cfg,
        params: Any,
        num_slots: int = 4,
        mesh=None,
        telemetry_recorder=None,
        async_decode: Optional[bool] = None,
        prefix_reuse: Optional[bool] = None,
        prefix_min: Optional[int] = None,
        paged: Optional[bool] = None,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        max_pages_per_req: Optional[int] = None,
        tier: Optional[bool] = None,
        tier_host_pages: Optional[int] = None,
        tier_low_water_pct: Optional[float] = None,
    ):
        from maggy_tpu.models import Decoder

        if cfg.decode:
            raise BadArgumentsError(
                "pass the TRAINING config; the engine builds the decode "
                "variant itself"
            )
        self.cfg = cfg
        self.decode_model = Decoder(dataclasses.replace(cfg, decode=True))
        self.params = params
        self.mesh = mesh
        self.slots = SlotManager(num_slots)
        self.max_seq_len = int(cfg.max_seq_len)
        self.telemetry = telemetry_recorder or telemetry.get()

        if async_decode is None:
            async_decode = os.environ.get(
                "MAGGY_TPU_SERVE_ASYNC", "1"
            ).lower() not in ("0", "false", "off")
        self.async_decode = async_decode

        if prefix_reuse is None:
            prefix_reuse = os.environ.get(
                "MAGGY_TPU_SERVE_PREFIX", "1"
            ).lower() not in ("0", "false", "off")
        self.prefix_reuse = prefix_reuse
        if prefix_min is None:
            prefix_min = int(
                os.environ.get("MAGGY_TPU_SERVE_PREFIX_MIN", MIN_PREFILL_BUCKET)
            )
        self.prefix_min = max(1, int(prefix_min))
        self.prefix_index = PrefixIndex(min_len=self.prefix_min)
        # prefix-reuse accounting (scheduler stats + SSTATS + telemetry)
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0
        self.prefill_calls = 0  # full (from-scratch) prefills
        # prompt tokens ACTUALLY computed by prefill (suffix-only on any
        # reuse path) — the figure to compare across affinity settings
        self.prefill_tokens = 0

        # ---- paged KV cache (docs/serving.md "Paged KV cache")
        if paged is None:
            paged = os.environ.get(
                "MAGGY_TPU_SERVE_PAGED", "1"
            ).lower() not in ("0", "false", "off")
        self.paged = bool(paged)
        if page_size is None:
            page_size = int(
                os.environ.get("MAGGY_TPU_SERVE_PAGE_SIZE", DEFAULT_PAGE_SIZE)
            )
        self.page_size = max(1, int(page_size))
        while self.max_seq_len % self.page_size:
            # any max_seq_len is served: fall back to the largest divisor
            self.page_size //= 2
        self.pages_per_row = self.max_seq_len // self.page_size
        # pool defaults to the dense capacity (num_slots full rows) plus the
        # reserved scratch page; pass num_pages to run UNDER the dense
        # budget — that is the whole point
        self._num_pages_explicit = num_pages is not None
        self.num_pages = (
            int(num_pages)
            if num_pages is not None
            else num_slots * self.pages_per_row + 1
        )
        self.max_pages_per_req = min(
            self.pages_per_row,
            int(max_pages_per_req)
            if max_pages_per_req is not None
            else self.pages_per_row,
        )
        self.pages_aliased = 0  # cumulative pages shared instead of copied
        self._last_page_gauges = None
        # per-slot high-water page count while resident — the
        # ``pages_held_peak`` figure trace attribution (v2) records per
        # request; cleared with the slot in release()
        self._peak_pages: Dict[int, int] = {}
        if self.paged:
            self.paged_model = Decoder(
                dataclasses.replace(
                    cfg,
                    decode=True,
                    paged=True,
                    page_size=self.page_size,
                    num_pages=self.num_pages,
                )
            )
            self.allocator = BlockAllocator(self.num_pages, self.page_size)
            self.page_table = PageTable(num_slots, self.pages_per_row)
        else:
            self.paged_model = None
            self.allocator = None
            self.page_table = None
        # the model behind the batch decode step (prefill always runs the
        # dense single-row variant; paged admission re-pages its output)
        self._batch_model = self.paged_model or self.decode_model

        # ---- host-DRAM KV tier (docs/serving.md "Host-DRAM page tier")
        if tier is None:
            tier = os.environ.get(
                "MAGGY_TPU_SERVE_TIER", "1"
            ).lower() not in ("0", "false", "off")
        self._tier_pages_explicit = tier_host_pages is not None
        if self.paged and tier:
            if tier_host_pages is None:
                tier_host_pages = int(
                    os.environ.get(
                        "MAGGY_TPU_SERVE_TIER_PAGES", 2 * self.num_pages
                    )
                )
            self.tier = HostPagePool(
                int(tier_host_pages), telemetry_recorder=self.telemetry
            )
            self.tier_policy = (
                TieringPolicy(low_water_pct=float(tier_low_water_pct))
                if tier_low_water_pct is not None
                else TieringPolicy()
            )
        else:
            # dense mode has no page-granular KV to spill; the tier is a
            # paged-cache feature, quietly off otherwise
            self.tier = None
            self.tier_policy = None

        B = num_slots
        dummy = jnp.zeros((B, 1), jnp.int32)
        self.cache = init_cache(self._batch_model, dummy, mesh=mesh)
        # decode applies run under the mesh so activation constraints and the
        # sharded cache resolve; mesh-free (single chip / CPU) costs nothing
        self._ctx = (lambda: mesh) if mesh is not None else contextlib.nullcontext
        # Host-built inputs of the decode step join the mesh, replicated: the
        # mesh an array lives on is part of jit's trace key, so a step fed now
        # a host array and now the previous step's own (mesh-placed) output
        # would be traced and compiled once per mixture
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            replicated = NamedSharding(mesh, PartitionSpec())
            self._put = lambda x: jax.device_put(x, replicated)
        else:
            self._put = jnp.asarray
        self.key_data = self._put(np.zeros((B, 2), np.uint32))
        # async double buffer: the dispatched-but-undrained decode step —
        # its device token refs plus (slot -> request id) at dispatch time,
        # so a drain can discard rows whose slot churned in the meantime
        self._pending: Optional[Dict[str, Any]] = None
        self._zero_tokens = self._put(np.zeros((B,), np.int32))
        # last async-drain host cost, sampled by the autopilot's serve
        # diagnoser (the gauge of the same name feeds dashboards)
        self.last_drain_ms = 0.0

        # trace-time side effects: these counters tick ONLY when jax retraces
        # the function, so they count compiles, not calls — the acceptance
        # telemetry that proves the decode step never recompiles under churn
        self._decode_traces = 0
        self._prefill_traces = 0
        self._admit_traces = 0
        self._prefix_traces = 0
        self._last_compile_counts = None
        self.last_compile_ts = time.time()  # when a trace counter last rose

        self._decode_jit = jax.jit(self._decode_impl)
        self._admit_jit = jax.jit(self._admit_impl)
        self._prefill_jit = jax.jit(self._prefill_impl)
        self._prefix_admit_jit = jax.jit(self._prefix_admit_impl)
        self._paged_admit_jit = jax.jit(self._paged_admit_impl)
        self._paged_prefix_admit_jit = jax.jit(self._paged_prefix_admit_impl)
        # abstract single-row cache: the leaf-shape template the prefix-admit
        # extraction uses to find each leaf's batch axis (mirrors _admit_impl)
        self._row_abstract = jax.eval_shape(
            lambda: init_cache(self.decode_model, jnp.zeros((1, 1), jnp.int32))
        )

        self.steps = 0
        self.tokens_out = 0

        # capacity ledger: the engine's share of HBM, reconciled at 1 Hz by
        # the scheduler's metrics tick (telemetry/memtrack.py)
        self.memory = memtrack.MemoryLedger()
        self._register_memory_accounts()

    def _register_memory_accounts(self) -> None:
        """(Re)register this engine's ledger accounts from live array sizes;
        called at build and after every reconfigure so the figures track the
        actual geometry (register is idempotent — no double counting)."""
        self.memory.register("params", memtrack.array_bytes(self.params))
        cache_bytes = memtrack.array_bytes(self.cache)
        self.memory.register("kv_pages", cache_bytes)
        self.memory.register(
            "workspace",
            memtrack.array_bytes(self.key_data)
            + memtrack.array_bytes(self._zero_tokens),
        )
        # KV bytes one resident token pins, from the real cache geometry —
        # sizes the prefix residency view (serve/prefix.py)
        cap_tokens = (
            self.num_pages * self.page_size
            if self.paged
            else self.slots.num_slots * self.max_seq_len
        )
        self.prefix_index.bytes_per_token = max(1, cache_bytes // max(1, cap_tokens))

    # ------------------------------------------------------------- jit bodies

    def _prefill_impl(self, params, tokens, plen, temp, top_k, key_data, gen0):
        """tokens [1, Pp] (bucket-padded), plen scalar — returns the filled
        single-row cache and the first sampled token. ``gen0`` is the
        generated-token index the sample resumes at: 0 for a fresh request,
        the retained token count for a preempted request being re-admitted
        from prompt+generated tokens (the PRNG chain continues exactly
        where decode would have — docs/serving.md "Preemption")."""
        self._prefill_traces += 1
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
        )
        logits, cache = prefill(self.decode_model, params, tokens, positions)
        last = jax.lax.dynamic_index_in_dim(
            logits[0], plen - 1, axis=0, keepdims=False
        )  # [V] — the logit that predicts the next generated token
        key = jax.random.fold_in(jax.random.wrap_key_data(key_data), gen0)
        tok = _sample_one(last, temp, top_k, key)
        return cache, tok

    def _admit_impl(
        self, cache, row_cache, key_data, slot, plen, key_pair, own_program=True
    ):
        """Copy the prefilled single-row cache into batch row ``slot`` and pin
        that row's write index to the true prompt length. Traced inside the
        prefix-admit program (``own_program=False``) it counts towards that
        program's compiles, not ``admit``'s: the recompile sentinel holds
        ``admit`` to one compile."""
        if own_program:
            self._admit_traces += 1

        def write(path, batch_leaf, row_leaf):
            if "index" in jax.tree_util.keystr(path):
                row = jnp.full_like(row_leaf, plen)
            else:
                row = row_leaf
            # the batch axis is the one whose extent differs (1 vs B); with
            # B == 1 the shapes tie and slot can only be 0, so axis choice
            # is irrelevant
            axis = next(
                (
                    i
                    for i, (a, b) in enumerate(zip(batch_leaf.shape, row.shape))
                    if a != b
                ),
                0,
            )
            starts = [jnp.int32(0)] * batch_leaf.ndim
            starts[axis] = slot
            return jax.lax.dynamic_update_slice(batch_leaf, row, starts)

        cache = jax.tree_util.tree_map_with_path(write, cache, row_cache)
        key_data = jax.lax.dynamic_update_slice(
            key_data, key_pair[None, :], (slot, jnp.int32(0))
        )
        return cache, key_data

    def _prefix_admit_impl(
        self,
        params,
        cache,
        key_data,
        src_slot,
        dst_slot,
        suffix_tokens,
        start,
        plen,
        gen0,
        temp,
        top_k,
        key_pair,
    ):
        """Admit-from-prefix, one compiled program per suffix bucket: extract
        batch row ``src_slot`` as a single-row cache whose write index is
        pinned to ``start`` (the shared-prefix length — rows above it are the
        source's own suffix/generated K/V, masked exactly like prefill pad
        garbage), prefill ONLY the suffix through it (positions
        ``start..start+Sb``), sample the first token from the last valid
        suffix logit, and copy the row into ``dst_slot`` via the admit body.

        ``start``/``plen`` are traced scalars, so reuse length never
        retraces; only the suffix bucket shape does (same O(log) compile
        ladder as full prefill)."""
        self._prefix_traces += 1

        def extract(path, batch_leaf, row_ab):
            if "index" in jax.tree_util.keystr(path):
                return jnp.full(row_ab.shape, start, row_ab.dtype)
            axis = next(
                (
                    i
                    for i, (a, r) in enumerate(
                        zip(batch_leaf.shape, row_ab.shape)
                    )
                    if a != r
                ),
                0,
            )
            starts = [jnp.int32(0)] * batch_leaf.ndim
            starts[axis] = src_slot
            return jax.lax.dynamic_slice(batch_leaf, starts, row_ab.shape)

        row_cache = jax.tree_util.tree_map_with_path(
            extract, cache, self._row_abstract
        )
        positions = (start + jnp.arange(suffix_tokens.shape[1], dtype=jnp.int32))[
            None, :
        ]
        logits, mutated = self.decode_model.apply(
            {"params": params, "cache": row_cache},
            suffix_tokens,
            positions,
            mutable=["cache"],
        )
        last = jax.lax.dynamic_index_in_dim(
            logits[0], plen - start - 1, axis=0, keepdims=False
        )  # [V] — the logit at overall position plen-1, same as full prefill
        key = jax.random.fold_in(jax.random.wrap_key_data(key_pair), gen0)
        tok = _sample_one(last, temp, top_k, key)
        cache, key_data = self._admit_impl(
            cache, mutated["cache"], key_data, dst_slot, plen, key_pair,
            own_program=False,
        )
        return cache, key_data, tok

    # ------------------------------------------------------ paged jit bodies

    def _paged_admit_impl(
        self, cache, row_cache, key_data, write_ids, slot, plen, key_pair,
        own_program=True,
    ):
        """Write a prefilled dense single-row cache into the page pool.

        ``write_ids`` is a ``[pages_per_row]`` int32 host-built map: entry
        ``j`` is the physical page that receives the row's logical page
        ``j``, or the scratch page 0 for pages this request does not own —
        prefix-ALIASED pages (their content is already correct and shared;
        writing them would violate copy-on-write) and pages past the
        prompt. Scratch writes are garbage by contract; real pages receive
        a FULL page of row content, so the write is idempotent against any
        masked garbage an in-flight async step may have scattered there.
        ``own_program``: as in :meth:`_admit_impl`."""
        if own_program:
            self._admit_traces += 1
        row = {
            jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(row_cache)[0]
        }

        def write(path, leaf):
            ks = jax.tree_util.keystr(path)
            if "pages" in ks:
                return leaf  # host-owned: the engine pushes the table
            if "index" in ks:
                b = leaf.shape[-1]
                return jnp.where(jnp.arange(b) == slot, plen, leaf)
            rl = row[ks]  # [(L,) 1, S, Kh, Dh] dense row
            P = leaf.shape[-3]
            if leaf.ndim == 5:  # scanned pool [L, N, P, Kh, Dh]
                pages = rl[:, 0].reshape(
                    rl.shape[0], -1, P, *rl.shape[3:]
                )
                return leaf.at[:, write_ids].set(pages.astype(leaf.dtype))
            pages = rl[0].reshape(-1, P, *rl.shape[3:])
            return leaf.at[write_ids].set(pages.astype(leaf.dtype))

        cache = jax.tree_util.tree_map_with_path(write, cache)
        key_data = jax.lax.dynamic_update_slice(
            key_data, key_pair[None, :], (slot, jnp.int32(0))
        )
        return cache, key_data

    def _paged_prefix_admit_impl(
        self,
        params,
        cache,
        key_data,
        src_row_ids,
        write_ids,
        dst_slot,
        suffix_tokens,
        start,
        plen,
        gen0,
        temp,
        top_k,
        key_pair,
    ):
        """Paged admit-from-prefix, one compiled program per suffix bucket.

        The source request's page-table row (``src_row_ids``) gathers its
        pool pages back into a dense single-row workspace whose index is
        pinned to ``start``; ONLY the suffix runs through the model
        (positions ``start..plen``), and the mutated row is re-paged via
        ``write_ids`` — which routes the shared full pages to scratch, so
        the aliased pages are never rewritten (zero KV copies for the
        shared prefix; the partial boundary page is the one copy, carried
        through the workspace). The persistent sharing is pure host state:
        the allocator ref-counts the aliased page ids into the new
        request's page list before this program runs."""
        self._prefix_traces += 1
        pooled = {
            jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
        }

        def extract(path, row_ab):
            ks = jax.tree_util.keystr(path)
            if "index" in ks:
                return jnp.full(row_ab.shape, start, row_ab.dtype)
            leaf = pooled[ks]
            if leaf.ndim == 5:  # scanned pool [L, N, P, Kh, Dh]
                return leaf[:, src_row_ids].reshape(row_ab.shape)
            return leaf[src_row_ids].reshape(row_ab.shape)

        row_cache = jax.tree_util.tree_map_with_path(
            extract, self._row_abstract
        )
        positions = (start + jnp.arange(suffix_tokens.shape[1], dtype=jnp.int32))[
            None, :
        ]
        logits, mutated = self.decode_model.apply(
            {"params": params, "cache": row_cache},
            suffix_tokens,
            positions,
            mutable=["cache"],
        )
        last = jax.lax.dynamic_index_in_dim(
            logits[0], plen - start - 1, axis=0, keepdims=False
        )
        key = jax.random.fold_in(jax.random.wrap_key_data(key_pair), gen0)
        tok = _sample_one(last, temp, top_k, key)
        cache, key_data = self._paged_admit_impl(
            cache, mutated["cache"], key_data, write_ids, dst_slot, plen,
            key_pair, own_program=False,
        )
        return cache, key_data, tok

    def _decode_impl(
        self,
        params,
        cache,
        key_data,
        prev_tokens,
        host_tokens,
        use_prev,
        pos,
        active,
        temp,
        top_k,
        gen_idx,
    ):
        """One token for every slot; inactive rows run masked (their cache
        index is reset to 0 afterwards so they never inflate the chunked
        cache-read bound or run past max_seq_len).

        ``prev_tokens`` is the previous dispatch's on-device sampled output;
        rows with ``use_prev`` feed it forward directly (async double
        buffer — the value never visits the host), the rest (fresh
        admissions, and every row on the synchronous path) take
        ``host_tokens``."""
        self._decode_traces += 1
        tokens = jnp.where(use_prev, prev_tokens, host_tokens)
        logits, mutated = self._batch_model.apply(
            {"params": params, "cache": cache},
            tokens[:, None],
            pos[:, None],
            mutable=["cache"],
        )
        cache = mutated["cache"]

        keys = jax.vmap(jax.random.fold_in)(
            jax.random.wrap_key_data(key_data), gen_idx
        )
        sampled = jax.vmap(_sample_one)(logits[:, 0], temp, top_k, keys)
        sampled = jnp.where(active, sampled, 0)

        def clamp_index(path, leaf):
            if "index" in jax.tree_util.keystr(path):
                return jnp.where(active, leaf, 0)
            return leaf

        cache = jax.tree_util.tree_map_with_path(clamp_index, cache)
        # advanced coordinates for the steady-state async fast path: while
        # the slot set is unchanged, the next dispatch reuses these device
        # refs verbatim — zero host arrays built or transferred per token
        next_pos = jnp.where(active, pos + 1, pos)
        next_gen = jnp.where(active, gen_idx + 1, gen_idx)
        return cache, sampled, next_pos, next_gen

    # -------------------------------------------------------------- admission

    def _bucket(self, plen: int) -> int:
        b = MIN_PREFILL_BUCKET
        while b < plen:
            b *= 2
        return min(b, self.max_seq_len)

    def admit(self, request: Request) -> Tuple[int, int]:
        """Prefill ``request``'s prompt and claim a free slot for it.

        Returns ``(slot, first_token)`` — the first token IS the TTFT token,
        produced here, not in the decode loop. Raises
        :class:`SlotOccupiedError` when no slot is free,
        :class:`OutOfPagesError` when the paged pool cannot hold the prompt
        (the scheduler's cue to wait or preempt — never a failed request),
        and :class:`BadArgumentsError` when the request cannot fit at all.

        A request carrying generated tokens is a PREEMPTED request being
        re-admitted: the effective prompt is prompt+tokens and the sampling
        chain resumes at ``gen0 = len(tokens)``, so the continued stream is
        byte-identical to one that was never preempted.
        """
        prompt = [int(t) for t in request.prompt] + [
            int(t) for t in request.tokens
        ]
        gen0 = len(request.tokens)
        plen = len(prompt)
        p = request.params
        if len(request.prompt) < 1:
            raise BadArgumentsError("empty prompt")
        if len(request.prompt) + p.max_new > self.max_seq_len:
            raise BadArgumentsError(
                f"prompt ({len(request.prompt)}) + max_new ({p.max_new}) "
                f"exceeds max_seq_len ({self.max_seq_len})"
            )
        if not self.slots.free_slots():
            raise SlotOccupiedError("no free slot")
        if self.paged:
            worst = -(-(len(request.prompt) + p.max_new) // self.page_size)
            cap = min(self.max_pages_per_req, self.allocator.pages_total)
            if worst > cap:
                raise BadArgumentsError(
                    f"request needs up to {worst} pages "
                    f"(page_size {self.page_size}) > cap {cap} "
                    "(max_pages_per_req / pool size)"
                )

        key_pair = jnp.asarray(_base_key_data(p.seed))
        slot = self.slots.free_slots()[0]
        reuse = self._match_prefix(prompt)
        tok = None
        if self.tier is not None:
            tok = self._try_tier_admit(
                prompt, p, slot, gen0, reuse, key_pair, request
            )
        if tok is None:
            if self.paged:
                tok = self._admit_paged(prompt, p, slot, gen0, reuse, key_pair)
            else:
                tok = self._admit_dense(prompt, p, slot, gen0, reuse, key_pair)
        # claim the slot only after every device op succeeded — a throwing
        # prefill/admit must not leak an occupied slot bound to a dead request
        first = int(tok)
        assert (
            self.slots.admit(request, first, next_pos=plen, generated=gen0 + 1)
            == slot
        )
        self.prefix_index.insert(slot, prompt, gen=self.steps)
        self.tokens_out += 1
        self._record_compile_gauges()
        return slot, first

    def _admit_dense(self, prompt, p, slot, gen0, reuse, key_pair):
        """Dense-mode admission: full-row copy into the batch cache."""
        plen = len(prompt)
        if reuse is not None:
            src, shared = reuse
            # the suffix bucket must still fit above the shared rows — cap it
            # so the per-row cache write can never be position-clamped
            bucket = min(self._bucket(plen - shared), self.max_seq_len - shared)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, : plen - shared] = prompt[shared:]
            with self.telemetry.span(
                "serve.prefix_admit", bucket=bucket, shared=shared
            ), self._ctx():
                self.cache, self.key_data, tok = self._prefix_admit_jit(
                    self.params,
                    self.cache,
                    self.key_data,
                    jnp.int32(src),
                    jnp.int32(slot),
                    jnp.asarray(padded),
                    jnp.int32(shared),
                    jnp.int32(plen),
                    jnp.int32(gen0),
                    jnp.float32(p.temperature),
                    jnp.int32(p.top_k),
                    key_pair,
                )
            self._note_prefix_hit(shared, 0)
            self.prefill_tokens += plen - shared
        else:
            bucket = self._bucket(plen)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = prompt
            with self.telemetry.span("serve.prefill", bucket=bucket), self._ctx():
                row_cache, tok = self._prefill_jit(
                    self.params,
                    jnp.asarray(padded),
                    jnp.int32(plen),
                    jnp.float32(p.temperature),
                    jnp.int32(p.top_k),
                    key_pair,
                    jnp.int32(gen0),
                )
                self.cache, self.key_data = self._admit_jit(
                    self.cache,
                    row_cache,
                    self.key_data,
                    jnp.int32(slot),
                    jnp.int32(plen),
                    key_pair,
                )
            self.prefill_calls += 1
            self.prefill_tokens += plen
        return tok

    def _admit_paged(self, prompt, p, slot, gen0, reuse, key_pair):
        """Paged admission: allocate the prompt's pages (aliasing the shared
        full pages on a prefix hit), prefill (suffix-only on a hit), and
        re-page the resulting dense row through ``write_ids``. Allocation is
        rolled back if any device op throws, so a poison request leaks
        nothing."""
        plen = len(prompt)
        P = self.page_size
        n_prompt_pages = -(-plen // P)
        write_ids = np.zeros((self.pages_per_row,), np.int32)
        if reuse is not None:
            src, shared = reuse
            src_pages = self.page_table.pages(src)
            # full pages covered by the shared prefix are aliased; the
            # partial boundary page (if any) is copy-on-write — a fresh
            # page written from the workspace row
            shared_full = min(shared // P, len(src_pages), n_prompt_pages)
            fresh = self.allocator.alloc(n_prompt_pages - shared_full)
            aliased = src_pages[:shared_full]
            try:
                self.allocator.share(aliased)
            except Exception:
                self.allocator.release(fresh)
                raise
            page_list = aliased + fresh
            write_ids[shared_full:n_prompt_pages] = fresh
            bucket = min(self._bucket(plen - shared), self.max_seq_len - shared)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, : plen - shared] = prompt[shared:]
            try:
                with self.telemetry.span(
                    "serve.prefix_admit", bucket=bucket, shared=shared
                ), self._ctx():
                    self.cache, self.key_data, tok = self._paged_prefix_admit_jit(
                        self.params,
                        self.cache,
                        self.key_data,
                        jnp.asarray(self.page_table.row(src)),
                        jnp.asarray(write_ids),
                        jnp.int32(slot),
                        jnp.asarray(padded),
                        jnp.int32(shared),
                        jnp.int32(plen),
                        jnp.int32(gen0),
                        jnp.float32(p.temperature),
                        jnp.int32(p.top_k),
                        key_pair,
                    )
            except Exception:
                self.allocator.release(page_list)
                raise
            self._note_prefix_hit(shared, shared_full)
            self.prefill_tokens += plen - shared
        else:
            fresh = self.allocator.alloc(n_prompt_pages)
            page_list = fresh
            write_ids[:n_prompt_pages] = fresh
            bucket = self._bucket(plen)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = prompt
            try:
                with self.telemetry.span(
                    "serve.prefill", bucket=bucket
                ), self._ctx():
                    row_cache, tok = self._prefill_jit(
                        self.params,
                        jnp.asarray(padded),
                        jnp.int32(plen),
                        jnp.float32(p.temperature),
                        jnp.int32(p.top_k),
                        key_pair,
                        jnp.int32(gen0),
                    )
                    self.cache, self.key_data = self._paged_admit_jit(
                        self.cache,
                        row_cache,
                        self.key_data,
                        jnp.asarray(write_ids),
                        jnp.int32(slot),
                        jnp.int32(plen),
                        key_pair,
                    )
            except Exception:
                self.allocator.release(fresh)
                raise
            self.prefill_calls += 1
            self.prefill_tokens += plen
        self.page_table.assign(slot, page_list)
        self.allocator.touch(page_list, self.steps)
        self._peak_pages[slot] = len(page_list)
        self._push_page_table()
        self._pages_gauges()
        return tok

    def _note_prefix_hit(self, shared: int, shared_full_pages: int) -> None:
        self.prefix_hits += 1
        self.prefix_tokens_saved += shared
        self.pages_aliased += shared_full_pages
        self.telemetry.count("serve.prefix_hits")
        self.telemetry.count("serve.prefix_tokens_saved", shared)

    def _match_prefix(self, prompt) -> Optional[Tuple[int, int]]:
        """``(src_slot, shared_len)`` when a resident slot shares a usable
        prefix with ``prompt``. The shared length is clamped to ``plen - 1``:
        at least one suffix token must run through the model to produce the
        logit that samples the request's first token."""
        if not self.prefix_reuse:
            return None
        m = self.prefix_index.match(prompt, gen=self.steps)
        if m is None:
            return None
        src, lcp = m
        shared = min(lcp, len(prompt) - 1)
        if shared < self.prefix_min:
            return None
        return src, shared

    # ------------------------------------------------- host-DRAM KV tier

    def _tier_capture_pages(self, page_ids) -> Dict[str, np.ndarray]:
        """Device→host copy of the pool pages ``page_ids``, one
        ``[n, P, Kh, Dh]`` block stack per cache leaf (scanned leaves
        carry the layer axis in front: ``[n, L, P, Kh, Dh]``). Same
        ``jax.device_get`` serialization seam as the disaggregated
        prefill pack, so bytes survive the round trip."""
        ids = [int(p) for p in page_ids]
        blocks: Dict[str, np.ndarray] = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(self.cache)[0]:
            ks = jax.tree_util.keystr(path)
            if "pages" in ks or "index" in ks:
                continue  # host-owned table / per-row write index
            if leaf.ndim == 5:  # scanned pool [L, N, P, Kh, Dh]
                blocks[ks] = np.moveaxis(jax.device_get(leaf[:, ids]), 1, 0)
            else:  # [N, P, Kh, Dh]
                blocks[ks] = jax.device_get(leaf[ids])
        return blocks

    def _tier_write_pages(self, page_ids, blocks) -> None:
        """Scatter host page blocks back into the device pool at
        ``page_ids`` — the eager inverse of :meth:`_tier_capture_pages`,
        run before the compiled suffix-admit gathers through them."""
        ids = jnp.asarray([int(p) for p in page_ids], jnp.int32)

        def write(path, leaf):
            ks = jax.tree_util.keystr(path)
            if ks not in blocks:
                return leaf
            b = blocks[ks]
            if leaf.ndim == 5:
                return leaf.at[:, ids].set(
                    jnp.asarray(np.moveaxis(b, 0, 1), leaf.dtype)
                )
            return leaf.at[ids].set(jnp.asarray(b, leaf.dtype))

        self.cache = jax.tree_util.tree_map_with_path(write, self.cache)

    def spill_stream(self, slot: int, pressure: bool = False) -> bool:
        """Capture a resident stream's valid KV pages into the host tier
        as a resume pack (``rid:<id>``) — the scheduler calls this
        immediately BEFORE preempt-releasing the slot, so re-admission
        becomes a swap-in instead of a full re-prefill. Valid rows are
        ``[0, len(prompt+tokens) - 1)``: prefill wrote the prompt's rows,
        each drained decode step wrote one more, and the newest sampled
        token was never fed back — exactly the rows re-prefill would
        recompute, so the swapped-in stream is byte-identical. False (no
        side effects) when the tier is off, the slot is empty, or the
        pack does not fit the host budget."""
        if self.tier is None:
            return False
        st = self.slots.get(slot)
        if st is None:
            return False
        tokens = [int(t) for t in st.request.prompt] + [
            int(t) for t in st.request.tokens
        ]
        valid = len(tokens) - 1
        if valid < 1:
            return False
        pages = self.page_table.pages(slot)
        need = (valid - 1) // self.page_size + 1
        if len(pages) < need:
            return False
        t0 = time.perf_counter()
        with self.telemetry.span("serve.spill", pages=need, kind="resume"):
            blocks = self._tier_capture_pages(pages[:need])
            ok = self.tier.put(
                f"rid:{st.request.id}",
                blocks,
                {"tokens": tuple(tokens), "valid": valid, "kind": "resume"},
            )
        if ok:
            self.tier_policy.note_spill(need, pressure=pressure)
            self.telemetry.count("tier.spills")
            self.telemetry.count("tier.spilled_pages", need)
            if pressure:
                self.telemetry.count("tier.pressure_spills")
            self.telemetry.histogram(
                "tier.spill_ms", (time.perf_counter() - t0) * 1e3
            )
        return ok

    def _spill_prefix(self, slot: int) -> None:
        """On release, park the departing prompt's full KV pages in the
        host tier as a prefix pack (``px:<digest>``) so a later request
        sharing the prefix swaps it in instead of re-prefilling — prefix
        reuse that survives eviction (docs/fleet.md "Fleet-global KV").
        Gated to prompts of at least one full page; best-effort."""
        if self.tier is None:
            return
        prompt = self.prefix_index.resident().get(slot)
        if not prompt:
            return
        prompt = tuple(int(t) for t in prompt)
        plen0 = len(prompt)
        if plen0 < self.page_size:
            return  # under one page: re-prefill beats a pack round-trip
        pages = self.page_table.pages(slot)
        valid = min(plen0, len(pages) * self.page_size)
        if valid < self.page_size:
            return
        need = (valid - 1) // self.page_size + 1
        t0 = time.perf_counter()
        # the copy-out runs on the scheduler's thread, the device waiting
        with self.telemetry.span("serve.spill", pages=need, kind="prefix"):
            blocks = self._tier_capture_pages(pages[:need])
            kept = self.tier.put(
                f"px:{PrefixIndex.digest(prompt)}",
                blocks,
                {"tokens": prompt, "valid": valid, "kind": "prefix"},
            )
        if kept:
            self.tier_policy.note_spill(need, prefix=True)
            self.telemetry.count("tier.spills")
            self.telemetry.count("tier.prefix_spills")
            self.telemetry.count("tier.spilled_pages", need)
            self.telemetry.histogram(
                "tier.spill_ms", (time.perf_counter() - t0) * 1e3
            )

    def _try_tier_admit(self, prompt, p, slot, gen0, reuse, key_pair, request):
        """Tier-first admission: a resume pack (exact token match on this
        request's id) wins outright; otherwise a prefix pack is used only
        when it covers MORE shared tokens than the device-resident prefix
        index would. Returns the first sampled token, or None to fall
        through to the normal admit paths."""
        plen = len(prompt)
        if gen0 > 0:
            key = f"rid:{request.id}"
            got = self.tier.get(key) if self.tier.has(key) else None
            if got is not None:
                blocks, meta = got
                start = int(meta.get("valid", 0))
                if (
                    meta.get("kind") == "resume"
                    and tuple(meta.get("tokens", ())) == tuple(prompt)
                    and 1 <= start <= plen - 1
                ):
                    t0 = time.perf_counter()
                    tok = self._tier_admit(
                        prompt, p, slot, gen0, key_pair, blocks, start
                    )
                    self.tier.drop(key)  # one resume per preemption
                    n = next(iter(blocks.values())).shape[0]
                    self.tier_policy.note_fill(n)
                    self.telemetry.count("tier.fills")
                    self.telemetry.count("tier.filled_pages", n)
                    self.telemetry.histogram(
                        "tier.swap_in_ms", (time.perf_counter() - t0) * 1e3
                    )
                    self.prefill_tokens += plen - start
                    return tok
                self.tier.drop(key)  # stale pack: request state moved on
        if not self.prefix_reuse or plen - 1 < self.prefix_min:
            return None
        key = f"px:{PrefixIndex.digest(prompt)}"
        got = self.tier.get(key) if self.tier.has(key) else None
        if got is None:
            return None
        blocks, meta = got
        mtok = tuple(meta.get("tokens", ()))
        shared = 0
        for a, b in zip(mtok, prompt):
            if a != b:
                break
            shared += 1
        shared = min(shared, int(meta.get("valid", 0)), plen - 1)
        dev_shared = reuse[1] if reuse is not None else 0
        if shared < self.prefix_min or shared <= dev_shared:
            return None  # digest collision, or HBM-resident reuse is better
        t0 = time.perf_counter()
        cover = (shared - 1) // self.page_size + 1
        tok = self._tier_admit(
            prompt, p, slot, gen0, key_pair,
            {ks: arr[:cover] for ks, arr in blocks.items()}, shared,
        )
        self.tier_policy.note_fill(cover, prefix=True)
        self.telemetry.count("tier.fills")
        self.telemetry.count("tier.prefix_fills")
        self.telemetry.count("tier.filled_pages", cover)
        self.telemetry.histogram(
            "tier.swap_in_ms", (time.perf_counter() - t0) * 1e3
        )
        self.prefill_tokens += plen - shared
        self._note_prefix_hit(shared, 0)
        return tok

    def _tier_admit(self, prompt, p, slot, gen0, key_pair, blocks, start):
        """Shared restore path for both pack kinds: materialize the
        pack's pages into freshly allocated pool pages, then run ONLY the
        suffix (positions ``start..plen``) through the existing compiled
        prefix-admit program — same bucket ladder, no new jit body, and
        byte-identical to a full prefill because the restored rows are
        the full prefill's own bytes."""
        plen = len(prompt)
        P = self.page_size
        n_prompt_pages = -(-plen // P)
        # pages carrying restored rows [0, start); the suffix writes from
        # the page containing row ``start`` upward (the boundary page is
        # re-written WHOLE from the workspace row, whose low rows are the
        # restored bytes — idempotent, like every paged admit)
        cover = (start - 1) // P + 1
        fresh = self.allocator.alloc(n_prompt_pages)
        try:
            self._tier_write_pages(
                fresh[:cover], {ks: arr[:cover] for ks, arr in blocks.items()}
            )
            src_row_ids = np.zeros((self.pages_per_row,), np.int32)
            src_row_ids[:cover] = fresh[:cover]
            write_ids = np.zeros((self.pages_per_row,), np.int32)
            boundary = start // P
            write_ids[boundary:n_prompt_pages] = fresh[boundary:n_prompt_pages]
            bucket = min(self._bucket(plen - start), self.max_seq_len - start)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, : plen - start] = prompt[start:]
            with self.telemetry.span(
                "serve.prefix_admit", bucket=bucket, shared=start
            ), self._ctx():
                self.cache, self.key_data, tok = self._paged_prefix_admit_jit(
                    self.params,
                    self.cache,
                    self.key_data,
                    jnp.asarray(src_row_ids),
                    jnp.asarray(write_ids),
                    jnp.int32(slot),
                    jnp.asarray(padded),
                    jnp.int32(start),
                    jnp.int32(plen),
                    jnp.int32(gen0),
                    jnp.float32(p.temperature),
                    jnp.int32(p.top_k),
                    key_pair,
                )
        except Exception:
            self.allocator.release(fresh)
            raise
        self.page_table.assign(slot, fresh)
        self.allocator.touch(fresh, self.steps)
        self._peak_pages[slot] = len(fresh)
        self._push_page_table()
        self._pages_gauges()
        return tok

    def reconfigure(self, num_slots: int) -> None:
        """Drain-and-reconfigure seam: rebuild the slot geometry with
        ``num_slots`` rows. Must be called with NO active slots (the
        scheduler drains between waves first — docs/autotune.md "Continuous
        tuning"); resident prefix anchors are dropped with the old cache.

        The existing jit wrappers are kept — jax retraces them for the new
        batch shape — and the decode step is warmed here with one
        all-inactive dispatch, so the recompile is paid inside the
        reconfigure (while the autopilot suppresses guard samples), not by
        the first live request on the new geometry."""
        num_slots = int(num_slots)
        if num_slots < 1:
            raise BadArgumentsError(f"num_slots must be >= 1, got {num_slots}")
        if self.slots.active_count:
            raise SlotOccupiedError(
                f"reconfigure with {self.slots.active_count} active slot(s); "
                "drain first"
            )
        self.flush()
        if num_slots == self.slots.num_slots:
            return
        B = num_slots
        self.slots = SlotManager(B)
        self.prefix_index = PrefixIndex(min_len=self.prefix_min)
        if self.paged:
            from maggy_tpu.models import Decoder

            # pool scales with the slot count unless the operator pinned an
            # explicit page budget (then more slots share the same HBM —
            # the paged trade the autopilot's num_slots moves exploit)
            if not self._num_pages_explicit:
                self.num_pages = B * self.pages_per_row + 1
            self.paged_model = Decoder(
                dataclasses.replace(
                    self.cfg,
                    decode=True,
                    paged=True,
                    page_size=self.page_size,
                    num_pages=self.num_pages,
                )
            )
            self._batch_model = self.paged_model
            self.allocator = BlockAllocator(self.num_pages, self.page_size)
            self.page_table = PageTable(B, self.pages_per_row)
            self._last_page_gauges = None
            # the host tier survives reconfigure — block shapes depend
            # only on page_size, and prefix packs are content-addressed —
            # but an un-pinned budget tracks the new pool size
            if self.tier is not None and not self._tier_pages_explicit:
                self.tier.set_capacity(2 * self.num_pages)
        self._peak_pages = {}
        self.cache = init_cache(
            self._batch_model, jnp.zeros((B, 1), jnp.int32), mesh=self.mesh
        )
        self._push_page_table()
        self.key_data = self._put(np.zeros((B, 2), np.uint32))
        self._zero_tokens = self._put(np.zeros((B,), np.int32))
        self._pending = None
        # warm the decode compile at the new geometry (all rows masked)
        zeros_i = self._zero_tokens
        zeros_b = self._put(np.zeros((B,), bool))
        with self.telemetry.span("serve.reconfigure", num_slots=B), self._ctx():
            self.cache, _, _, _ = jax.block_until_ready(
                self._decode_jit(
                    self.params, self.cache, self.key_data,
                    zeros_i, zeros_i, zeros_b, zeros_i,
                    zeros_b, self._put(np.zeros((B,), np.float32)),
                    zeros_i, zeros_i,
                )
            )
        self._record_compile_gauges()
        self._register_memory_accounts()

    def release(self, slot: int) -> Request:
        """Free a slot (EOS / max_new / cancel / deadline / preempt). THE
        one cache-resource release seam: every path that vacates a slot
        funnels through here, so pages and the prefix anchor can never leak
        on one exit path but not another. Pure host-side: the decode step
        zeroes inactive rows' cache index, paged writes of a cleared row
        are routed to the scratch page, and admission overwrites whole
        pages/rows."""
        if self.paged:
            if self.tier is not None:
                try:
                    self._spill_prefix(slot)
                except Exception:
                    pass  # best-effort: a failed spill never blocks release
            pages = self.page_table.clear(slot)
            if pages:
                self.allocator.release(pages)
            self._peak_pages.pop(slot, None)
            self._pages_gauges()
        self.prefix_index.remove(slot)
        return self.slots.evict(slot)

    def pages_held_peak(self, slot: int) -> int:
        """High-water page count of the request resident in ``slot`` (0 in
        dense mode). Read BEFORE :meth:`release` — the figure dies with the
        slot; the scheduler stamps it on the request's finish event for
        trace attribution (v2)."""
        return self._peak_pages.get(slot, 0)

    # ------------------------------------------------------------ page growth

    def prepare_step(self) -> List[int]:  # hot-loop (paged decode growth)
        """Paged only: make sure every active row owns the page its next
        write lands in (a row crosses a page boundary every ``page_size``
        tokens). Returns the slots whose growth the dry allocator refused —
        the scheduler preempts the youngest request and retries; an empty
        list means :meth:`step` is safe to dispatch. Dense mode returns
        ``[]`` unconditionally."""
        if not self.paged:
            return []
        needy: List[int] = []
        prev = self._pending
        P = self.page_size
        grew = False
        for s in self.slots.active_slots():
            st = self.slots.get(s)
            lag = (
                1
                if (
                    self.async_decode
                    and prev is not None
                    and prev["slots"].get(s) == st.request.id
                )
                else 0
            )
            need = (st.next_pos + lag) // P + 1
            while self.page_table.count(s) < need:
                try:
                    page = self.allocator.alloc(1)[0]
                except OutOfPagesError:
                    needy.append(s)
                    break
                self.page_table.grow(s, page)
                grew = True
            held = self.page_table.count(s)
            if held > self._peak_pages.get(s, 0):
                self._peak_pages[s] = held
            # heat stamp: an active row touches every page it holds this
            # step (attention reads them all) — host-side dict stores only
            self.allocator.touch(self.page_table.pages(s), self.steps)
        if grew:
            self._pages_gauges()
        return needy

    def _push_page_table(self) -> None:
        """Sync the host page-table mirror into the cache variable the
        compiled decode step gathers through. Cheap no-op unless admission,
        release, or growth dirtied the mirror — the steady-state decode
        fast path transfers nothing."""
        if not self.paged or not self.page_table.dirty:
            return
        tbl = self._put(self.page_table.table)

        def repl(path, leaf):
            if "pages" in jax.tree_util.keystr(path):
                return jnp.broadcast_to(tbl, leaf.shape)
            return leaf

        self.cache = jax.tree_util.tree_map_with_path(repl, self.cache)
        self.page_table.dirty = False

    def _pages_gauges(self) -> None:
        # journaled only on change, like the compile gauges: page counts
        # move at admission/release/boundary granularity, not per token
        a = self.allocator
        vals = (a.pages_free, a.pages_shared)
        if vals != self._last_page_gauges:
            self._last_page_gauges = vals
            self.telemetry.gauge("serve.pages_free", a.pages_free)
            self.telemetry.gauge("serve.pages_shared", a.pages_shared)

    # ----------------------------------------------------------------- decode

    def step(self) -> StepOutput:  # hot-loop (tools/check_host_sync.py)
        """Decode one token for every active slot.

        Synchronous mode returns THIS dispatch's tokens. Async mode (the
        default) returns the PREVIOUS dispatch's tokens — the new dispatch is
        issued first (its inputs chain from the in-flight device output), so
        the host-side drain/bookkeeping below overlaps device compute. With
        all slots free this degenerates to :meth:`flush`.
        """
        active_ids = self.slots.active_slots()
        if not active_ids:
            return self.flush()
        if self.paged:
            # page growth for this dispatch (no-op when the scheduler's
            # prepare_step/preempt pass already ran) + table sync if dirty
            needy = self.prepare_step()
            if needy:
                raise OutOfPagesError(
                    f"slots {needy} need pages and the pool is dry; "
                    "release or preempt before stepping"
                )
            self._push_page_table()
        prev = self._pending
        entries = {s: self.slots.get(s).request.id for s in active_ids}
        if (
            self.async_decode
            and prev is not None
            and prev["slots"] == entries
        ):
            # steady state (no churn since the last dispatch): every input
            # is a carried device ref — the previous step's own outputs.
            # use_prev == active (every live row continues its stream), so
            # no host array is built or transferred for this token at all.
            c = prev["carry"]
            inputs = (
                prev["sampled"], self._zero_tokens, c["active"], c["pos"],
                c["active"], c["temp"], c["top_k"], c["gen"],
            )
            carry_static = c
        else:
            B = self.slots.num_slots
            host_tokens = np.zeros((B,), np.int32)
            use_prev = np.zeros((B,), bool)
            pos = np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            temp = np.zeros((B,), np.float32)
            top_k = np.zeros((B,), np.int32)
            gen_idx = np.zeros((B,), np.int32)
            for s in active_ids:
                st = self.slots.get(s)
                # a slot still holding the request it held at the previous
                # dispatch has exactly ONE undrained token in flight: feed
                # it forward on-device and advance pos/gen_idx past it
                lag = 1 if (
                    self.async_decode
                    and prev is not None
                    and prev["slots"].get(s) == st.request.id
                ) else 0
                if lag:
                    use_prev[s] = True
                else:
                    host_tokens[s] = st.last_token
                pos[s] = st.next_pos + lag
                gen_idx[s] = st.generated + lag
                active[s] = True
                temp[s] = st.request.params.temperature
                top_k[s] = st.request.params.top_k
            prev_tokens = (
                prev["sampled"] if prev is not None else self._zero_tokens
            )
            active_dev = self._put(active)
            temp_dev = self._put(temp)
            top_k_dev = self._put(top_k)
            inputs = (
                prev_tokens, self._put(host_tokens), self._put(use_prev),
                self._put(pos), active_dev, temp_dev, top_k_dev,
                self._put(gen_idx),
            )
            carry_static = {
                "active": active_dev, "temp": temp_dev, "top_k": top_k_dev,
            }
        with self.telemetry.span("serve.decode_step", active=len(active_ids)), self._ctx():
            self.cache, sampled, next_pos, next_gen = self._decode_jit(
                self.params, self.cache, self.key_data, *inputs
            )
        self.steps += 1
        self._record_compile_gauges()
        dispatched = {
            "sampled": sampled,
            "slots": entries,
            "carry": {**carry_static, "pos": next_pos, "gen": next_gen},
        }
        if not self.async_decode:
            return self._drain(dispatched)
        self._pending = dispatched
        # drain the PREVIOUS step while this one crunches on the device
        return self._drain(prev)

    def flush(self) -> StepOutput:
        """Drain the in-flight async dispatch, if any. The scheduler calls
        this when the active set empties (and may call it before
        cancellation/deadline decisions that need host-current state); the
        synchronous path has nothing pending and returns an empty output."""
        prev, self._pending = self._pending, None
        return self._drain(prev)

    def _drain(self, pending: Optional[Dict[str, Any]]) -> StepOutput:
        """Host-read one dispatched step's tokens and advance the slot
        mirror. Rows whose slot was released or re-admitted since dispatch
        (the post-finish garbage step async mode inevitably runs) are
        discarded — slot/request identity gates every emit."""
        if pending is None:
            return StepOutput(tokens={})
        t0 = time.perf_counter()
        sampled = np.asarray(pending["sampled"])  # sync: ok — lagged double-buffer drain
        drain_ms = (time.perf_counter() - t0) * 1e3
        self.last_drain_ms = drain_ms
        self.telemetry.gauge("serve.drain_ms", drain_ms)
        self.telemetry.histogram("serve.drain_ms", drain_ms)
        out: Dict[int, int] = {}
        for s, rid in pending["slots"].items():
            st = self.slots.get(s)
            if st is None or st.request.id != rid:
                continue  # slot churned since dispatch; token belongs to no one
            tok = int(sampled[s])
            self.slots.advance(s, tok)
            out[s] = tok
        self.tokens_out += len(out)
        return StepOutput(tokens=out)

    # ------------------------------------------------- disaggregated prefill

    def prefill_only(self, prompt: List[int], params, gen0: int = 0) -> Dict[str, Any]:
        """The prefill half of disaggregated serving (docs/fleet.md
        "Disaggregated prefill/decode"): run one prompt through the
        single-row prefill program — slots, batch cache, and the page pool
        are untouched — and return a host-resident KV pack. The pack's
        leaves are numpy (``jax.device_get``), which IS the serialization
        boundary: a decode replica re-materializes them with a device put
        in :meth:`admit_from_kv`, exactly the checkpoint/device-put path.

        Byte-identity holds end to end because prefill output is a pure
        function of (params, prompt, seed) and the host round-trip
        preserves bits."""
        prompt = [int(t) for t in prompt]
        plen = len(prompt)
        if plen < 1:
            raise BadArgumentsError("empty prompt")
        if plen >= self.max_seq_len:
            raise BadArgumentsError(
                f"prompt ({plen}) exceeds max_seq_len ({self.max_seq_len})"
            )
        key_pair = jnp.asarray(_base_key_data(params.seed))
        bucket = self._bucket(plen)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = prompt
        with self.telemetry.span("serve.prefill", bucket=bucket), self._ctx():
            row_cache, tok = self._prefill_jit(
                self.params,
                jnp.asarray(padded),
                jnp.int32(plen),
                jnp.float32(params.temperature),
                jnp.int32(params.top_k),
                key_pair,
                jnp.int32(gen0),
            )
        self.prefill_calls += 1
        self.prefill_tokens += plen
        self._record_compile_gauges()
        return {
            "row": jax.device_get(row_cache),
            "plen": plen,
            "first": int(tok),
        }

    def admit_from_kv(self, request: Request, pack: Dict[str, Any]) -> Tuple[int, int]:
        """Admit a request whose prompt a PREFILL replica already ran: the
        pack's dense row is device-put here and written into the batch
        cache (re-paged through fresh pages in paged mode) — no model
        forward runs on this engine for the prompt. Returns
        ``(slot, first_token)``; the first token was sampled at prefill
        time and rides in the pack."""
        p = request.params
        plen = int(pack["plen"])
        if request.tokens or plen != len(request.prompt):
            raise BadArgumentsError(
                "KV pack does not match the request state (stale handoff)"
            )
        if plen + p.max_new > self.max_seq_len:
            raise BadArgumentsError(
                f"prompt ({plen}) + max_new ({p.max_new}) exceeds "
                f"max_seq_len ({self.max_seq_len})"
            )
        if not self.slots.free_slots():
            raise SlotOccupiedError("no free slot")
        key_pair = jnp.asarray(_base_key_data(p.seed))
        slot = self.slots.free_slots()[0]
        with self.telemetry.span("serve.kv_admit", plen=plen), self._ctx():
            row_cache = jax.tree.map(jnp.asarray, pack["row"])  # device put
            if self.paged:
                worst = -(-(plen + p.max_new) // self.page_size)
                cap = min(self.max_pages_per_req, self.allocator.pages_total)
                if worst > cap:
                    raise BadArgumentsError(
                        f"request needs up to {worst} pages > cap {cap}"
                    )
                n_prompt_pages = -(-plen // self.page_size)
                fresh = self.allocator.alloc(n_prompt_pages)
                write_ids = np.zeros((self.pages_per_row,), np.int32)
                write_ids[:n_prompt_pages] = fresh
                try:
                    self.cache, self.key_data = self._paged_admit_jit(
                        self.cache,
                        row_cache,
                        self.key_data,
                        jnp.asarray(write_ids),
                        jnp.int32(slot),
                        jnp.int32(plen),
                        key_pair,
                    )
                except Exception:
                    self.allocator.release(fresh)
                    raise
                self.page_table.assign(slot, fresh)
                self.allocator.touch(fresh, self.steps)
                self._peak_pages[slot] = len(fresh)
                self._push_page_table()
                self._pages_gauges()
            else:
                self.cache, self.key_data = self._admit_jit(
                    self.cache,
                    row_cache,
                    self.key_data,
                    jnp.int32(slot),
                    jnp.int32(plen),
                    key_pair,
                )
        first = int(pack["first"])
        assert self.slots.admit(request, first) == slot
        self.prefix_index.insert(
            slot, [int(t) for t in request.prompt], gen=self.steps
        )
        self.tokens_out += 1
        self._record_compile_gauges()
        return slot, first

    # -------------------------------------------------------------- telemetry

    def _record_compile_gauges(self) -> None:
        # on change only: the counters tick on RETRACES (rare by design).
        # The sentinel's ``compile.<program>`` series carry all four counts;
        # the gauge is the monitor panel's "decode compiled once" figure
        counts = tuple(self.compile_counts.values())
        if counts != self._last_compile_counts:
            self._last_compile_counts = counts
            self.last_compile_ts = time.time()
            self.telemetry.gauge("serve.decode_retraces", self._decode_traces)

    def warmed_up(self, now: float) -> bool:
        """The decode step has compiled and no program of this engine has
        for ``WARMUP_QUIET_S``: latencies read now are the engine's, not the
        compiler's. A new prefill bucket re-opens the window (its compile
        stalls every active row's decode)."""
        return (
            self._decode_traces > 0
            and now - self.last_compile_ts >= WARMUP_QUIET_S
        )

    @property
    def compile_counts(self) -> Dict[str, int]:
        return {
            "decode": self._decode_traces,
            "prefill": self._prefill_traces,
            "admit": self._admit_traces,
            "prefix_admit": self._prefix_traces,
        }

    @property
    def prefix_stats(self) -> Dict[str, Any]:
        """Reuse accounting for SSTATS/telemetry: hits, tokens the reuse
        saved from prefill, full prefills actually run, and the residency
        view (which prefixes pin how much KV, and how hot they are)."""
        return {
            "prefix_hits": self.prefix_hits,
            "prefix_tokens_saved": self.prefix_tokens_saved,
            "prefill_calls": self.prefill_calls,
            "prefill_tokens": self.prefill_tokens,
            "prefix_residency": self.prefix_index.residency_stats(
                gen=self.steps
            ),
        }

    @property
    def tier_stats(self) -> Dict[str, Any]:
        """Host-DRAM tier accounting for SSTATS and the monitor: pool
        occupancy plus the policy's spill/fill ledger. ``{"enabled":
        False}`` when the tier is off so panels can branch safely."""
        if self.tier is None:
            return {"enabled": False}
        return {
            "enabled": True,
            **self.tier.stats(),
            **self.tier_policy.stats(),
            # host-resident prefix digests, so the fleet prefix map counts
            # a spilled-but-swappable prefix as held by this replica
            "prefix_digests": [
                k[3:] for k in self.tier.keys() if k.startswith("px:")
            ],
        }

    @property
    def paging_stats(self) -> Dict[str, Any]:
        """Paged-cache accounting for SSTATS and the monitor: pool occupancy,
        sharing, and the per-request page cap. ``{"paged": False}`` on the
        dense fallback so panels can branch without key errors."""
        if not self.paged:
            return {"paged": False}
        return {
            "paged": True,
            "max_pages_per_req": self.max_pages_per_req,
            "pages_aliased_total": self.pages_aliased,
            **self.allocator.stats(),
            "fragmentation": self.allocator.fragmentation(),
            "heat": self.allocator.heat_buckets(self.steps),
        }

    def set_max_pages_per_req(self, value: int) -> None:
        """Autopilot seam (``serve.max_pages_per_req``, safe-live): caps how
        many pages ONE request may hold. Applies to future admissions and
        growth denials only — resident requests keep what they own."""
        self.max_pages_per_req = max(1, min(self.pages_per_row, int(value)))

    def set_tier_host_pages(self, value: int) -> None:
        """Autopilot seam (``serve.tier_host_pages``, safe-live): resize
        the host tier's page budget. Shrink evicts LRU packs immediately;
        an explicit value pins the budget across reconfigures."""
        if self.tier is None:
            return
        self._tier_pages_explicit = True
        self.tier.set_capacity(int(value))

    def set_tier_low_water(self, value: float) -> None:
        """Autopilot seam (``serve.tier_low_water_pct``, safe-live): move
        the pressure-spill trigger's headroom threshold."""
        if self.tier_policy is None:
            return
        self.tier_policy.low_water_pct = float(value)
