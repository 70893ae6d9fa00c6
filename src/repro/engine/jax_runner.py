"""Live JAX execution backend: the same Engine/tick loop, but ``run_batch``
really runs jit'd prefill/decode steps of a model on this host's device (a
published-width config on a TPU, a reduced one on the CPU) and returns
wall-clock seconds.

Two cache layouts, behind one ``_CacheLayout`` strategy surface:

* **paged** (default) — a *global pool* of KV pages ``(L, P+1, page, Hkv, D)``
  driven end-to-end by ``kvcache.pool.BlockPool`` block tables: the engine
  snapshots each batched session's lease into ``BatchWork.leases`` and the
  backend executes placement from those tables — prefill scatters chunk KV
  into leased pages and attends **gather-free** over the lease (the
  scalar-prefetched table steers the paged flash kernel's page reads in
  place; no dense ``pages[table]`` copy per chunk), decode feeds
  ``(B, max_pages)`` tables to the Pallas ``paged_attention`` kernel with
  the new token's KV write fused into its prologue (via
  ``ops.decode_attention``), copy-on-write events are mirrored as device
  page copies, and host offload moves KV *per block* (only private,
  non-shared blocks cross PCIe; shared prefix blocks are re-referenced on
  device at restore). Radix-shared prefix
  blocks are therefore **physically shared**: a K-session family over one
  repository context occupies ~ceil(L/page) + K*(private tail) pages. Page
  id P (one past the pool) is scratch: padded prefill lanes and idle decode
  lanes park their writes there.

* **dense** — the legacy slot-dense layout (R fixed slots, each a dense
  ``max_len``-token region; position ``max_len - 1`` is the slot's scratch).
  Kept for greedy-decode parity testing against the paged path, and as the
  fallback for attention variants the paged kernel doesn't cover
  (sliding-window alternation, logit softcaps).

Chunks and lane counts are bucketed to powers of two to bound
recompilation. The PerfOracle (recompute_time / prefill_rate / swap_time)
is *calibrated* at startup by timing one prefill chunk, one decode step and
one page/slot round trip — the live analogue of the simulator's analytic
model.
"""
from __future__ import annotations

import threading
import time
import zlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.session import Session
from repro.engine.backend import BatchWork
from repro.kvcache.disk_tier import DiskFileStore
from repro.kvcache.pool import DeviceBindingMap
from repro.kvcache.swap_stream import (SwapStream, TransferFuture,
                                       resolved_future)
from repro.models import model_zoo
from repro.models.config import ModelConfig
from repro.models.transformer import (KVCache, PagedKVCache, lm_decode_paged,
                                      lm_mixed_paged, lm_prefill_paged,
                                      lm_step, prefill_next_token,
                                      supports_paged)


def _bucket(n: int, lo: int = 32) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class JaxBackend:
    name = "jax"

    def __init__(self, cfg: ModelConfig, *, layout: str = "paged",
                 max_slots: int = 8, max_len: int = 1024,
                 total_pages: Optional[int] = None, page_size: int = 32,
                 seed: int = 0, dtype=jnp.float32, async_swap: bool = True,
                 disk_spool: Optional[str] = None):
        assert cfg.family in ("dense", "moe"), "live runner serves LM families"
        assert layout in ("paged", "dense")
        if layout == "paged" and not supports_paged(cfg):
            layout = "dense"          # window/softcap: kernel not applicable
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.dtype = dtype
        self.params = model_zoo.init(cfg, jax.random.PRNGKey(seed), dtype)
        self.layout = layout
        if layout == "paged":
            if total_pages is None:
                total_pages = max(1, max_slots * max_len // page_size)
            self._impl: "_CacheLayout" = _PagedLayout(self, total_pages,
                                                      page_size,
                                                      async_swap=async_swap,
                                                      disk_spool=disk_spool)
        else:
            self._impl = _DenseLayout(self)
        # prefix sharing needs placement to follow block ids physically;
        # a real decoder also needs the last prompt token's logits, so a
        # full prefix hit must still leave >= 1 token to compute
        self.supports_prefix_sharing = (layout == "paged")
        self.requires_last_token_compute = (layout == "paged")
        # async swap stream: D2H drains and H2D prefetches run on a
        # background worker; the engine gates restores on transfer futures
        # and defers sessions whose swap-in is unresolved (dense stays
        # synchronous — it is the serialized parity baseline)
        self.supports_async_swap = (layout == "paged" and async_swap)
        # live-path dispatch timing (repro.obs collects this): cumulative
        # wall seconds per run_batch phase + call counts, so the metrics
        # plane can attribute live tick time to swap launch / CoW mirror /
        # restore / prefill / decode dispatch without tracing every tick
        self.dispatch_stats: Dict[str, float] = {
            "batches": 0, "wall_s": 0.0,
            "swap_out_s": 0.0, "cow_s": 0.0, "swap_in_s": 0.0,
            "prefill_s": 0.0, "decode_s": 0.0,
            "prefill_calls": 0, "decode_calls": 0,
            # fused iteration-level ticks (mixed scheduler on the paged
            # layout): one dispatch covers both prefill packs + decode lanes
            "mixed_s": 0.0, "mixed_calls": 0,
            # analytic prefill HBM traffic: bytes the legacy gather path
            # would have touched vs bytes the in-place (block-table
            # steered) path touches; the paged layout accumulates both per
            # chunk so traces/benches can show the gather-free win
            "prefill_gather_bytes": 0.0, "prefill_inplace_bytes": 0.0,
        }
        self._impl.calibrate()

    # --- engine binding ---------------------------------------------------
    def bind_kv_pool(self, pool) -> None:
        """Engine handshake: validates that the engine's BlockPool fits the
        physical page pool (paged layout) — placement itself always arrives
        through ``BatchWork.leases`` snapshots, never live pool state."""
        self._impl.bind_kv_pool(pool)

    def release_session(self, sid: int) -> None:
        self._impl.release_session(sid)

    def drop_host(self, sid: int) -> None:
        self._impl.drop_host(sid)

    def prefetch_swap_in(self, sid: int) -> Optional[TransferFuture]:
        """Launch the H2D crossing of ``sid``'s private host blocks on the
        background stream (None when nothing private was offloaded). The
        engine defers the session until the returned future resolves, so
        the transfer overlaps the other sessions' compute."""
        return self._impl.prefetch_swap_in(sid)

    def spill_host(self, sid: int) -> Optional[TransferFuture]:
        """NVMe demotion data plane: write ``sid``'s host KV copy to the
        spool directory (freeing the DRAM copy) on the background stream.
        The TieredStore gates the disk entry on the returned future."""
        return self._impl.spill_host(sid)

    def fill_host(self, sid: int) -> Optional[TransferFuture]:
        """NVMe promotion data plane: read ``sid``'s spool file back into
        the host copy ahead of the PCIe swap-in."""
        return self._impl.fill_host(sid)

    def close(self) -> None:
        """Stop the background swap stream (benchmarks create several
        backends per process; daemon threads would otherwise pile up)."""
        self._impl.close()

    # --- oracle (calibrated) ----------------------------------------------
    def _time_once(self, fn) -> float:
        fn()                                      # compile
        t0 = time.monotonic()
        fn()
        return max(1e-6, time.monotonic() - t0)

    def recompute_time(self, n_tokens: int) -> float:
        return n_tokens * self._prefill_s_per_tok

    def prefill_rate(self) -> float:
        return 1.0 / self._prefill_s_per_tok

    def swap_time(self, n_tokens: int) -> float:
        """Measured host<->device KV bandwidth for the copy path."""
        return 1e-3 + n_tokens * self.kv_bytes_per_token() / self._h2d_bw

    def kv_bytes_per_token(self) -> float:
        return self._impl.kv_bytes_per_token()

    # --- execution --------------------------------------------------------
    def run_batch(self, work: BatchWork, now: float) -> float:
        if work.empty:
            return 0.0
        st = self.dispatch_stats
        t0 = time.monotonic()
        impl = self._impl
        # device-write ordering within a tick: D2H reads of swapped-out
        # pages first (their ids may be re-leased to this very batch), then
        # CoW page copies (their sources may be about to be overwritten),
        # then H2D restores, then compute writes. With the async stream the
        # D2H *snapshot* still happens here, in dispatch order (that is
        # what keeps re-leased page ids safe); only the host crossing moves
        # to the worker, and its future joins the swap-completion handshake
        for s, _toks in work.swapouts:
            fut = impl.swap_out(s)
            if fut is not None:
                work.swap_futures[s.sid] = fut
        t1 = time.monotonic()
        impl.apply_cow(work.cow_copies)
        t2 = time.monotonic()
        for s, _toks in work.swapins:
            impl.swap_in(s, work.leases.get(s.sid, ()))
        t3 = time.monotonic()
        fused = (work.mixed and (work.prefills or work.decodes)
                 and hasattr(impl, "run_mixed"))
        if fused:
            # iteration-level tick on the paged layout: prefill packs +
            # decode lanes share ONE jitted dispatch (attributed to
            # mixed_s; the phase split below keeps its legacy buckets for
            # the round path and non-paged layouts)
            impl.run_mixed(work)
            t4 = t5 = time.monotonic()
            st["mixed_s"] += t4 - t3
            st["mixed_calls"] += 1
        else:
            for s, chunk in work.prefills:
                impl.prefill(s, chunk, work.leases.get(s.sid, ()))
            t4 = time.monotonic()
            if work.decodes:
                impl.decodes(work.decodes, work.leases)
            t5 = time.monotonic()
            st["prefill_s"] += t4 - t3
            st["decode_s"] += t5 - t4
        st["batches"] += 1
        st["swap_out_s"] += t1 - t0
        st["cow_s"] += t2 - t1
        st["swap_in_s"] += t3 - t2
        st["wall_s"] += t5 - t0
        st["prefill_calls"] += len(work.prefills)
        st["decode_calls"] += len(work.decodes)
        return t5 - t0

    def swap_stream_stats(self) -> Optional[Dict]:
        """Background stream counters (None for the dense layout, which
        swaps synchronously) — absorbed by the metrics registry."""
        stream = getattr(self._impl, "stream", None)
        return stream.stats() if stream is not None else None

    def bind_cpu_pool(self, pool) -> None:
        """Engine hook: the swap stream's worker holds a core from the
        shared pool while a crossing executes, so pool gauges account real
        transfer CPU next to the tool threads. No-op without a stream."""
        stream = getattr(self._impl, "stream", None)
        if stream is not None:
            stream.cpu_pool = pool

    # --- deterministic synthetic context ----------------------------------
    def _context_ids(self, s: Session) -> List[int]:
        """Token ids are *content-addressed*: round-0 chunks derive from
        their prefix-hash keys (same chunk key => same tokens, so physically
        shared prefix pages hold exactly the bytes every family member would
        have computed) and everything beyond draws by (sid, absolute
        position) — re-entrant, so growing ``prefill_target`` after decode
        appends never re-draws earlier positions from the stream start."""
        ids = s.meta.setdefault("context_ids", [])
        target = s.prefill_target
        V = self.cfg.vocab_size
        hashes = s.meta.get("prefix_hashes")
        if hashes:
            round0 = sum(n for _, n in hashes)
            if len(ids) < round0:          # (no decode happened yet: round 0
                ids.clear()                #  must fully prefill first)
                for key, n in hashes:
                    rng = np.random.default_rng(
                        zlib.crc32(repr(key).encode()))
                    ids.extend(int(x) for x in rng.integers(2, V, size=n))
        while len(ids) < target:
            pos = len(ids)
            ids.append(int(np.random.default_rng((s.sid, pos))
                           .integers(2, V)))
        return ids


# ---------------------------------------------------------------------------
# layout strategies
# ---------------------------------------------------------------------------

class _CacheLayout:
    """Physical KV placement strategy: prefill/decode/swap/CoW execution.

    ``swap_out`` may return the transfer future of an asynchronously
    launched D2H drain (None == completed synchronously); ``prefetch_swap_in``
    launches the H2D crossing ahead of the restore (None == nothing private
    to move)."""

    def bind_kv_pool(self, pool) -> None: ...
    def calibrate(self) -> None: ...
    def kv_bytes_per_token(self) -> float: ...
    def release_session(self, sid: int) -> None: ...
    def drop_host(self, sid: int) -> None: ...
    def swap_out(self, s: Session) -> Optional[TransferFuture]: ...
    def swap_in(self, s: Session, lease) -> None: ...
    def prefetch_swap_in(self, sid: int) -> Optional[TransferFuture]:
        return None
    def spill_host(self, sid: int) -> Optional[TransferFuture]:
        return None           # layouts without an NVMe data plane: modeled
    def fill_host(self, sid: int) -> Optional[TransferFuture]:
        return None
    def apply_cow(self, copies) -> None: ...
    def prefill(self, s: Session, chunk: int, lease) -> None: ...
    def decodes(self, decodes, leases) -> None: ...
    def close(self) -> None: ...


class _PagedLayout(_CacheLayout):
    """Global page pool driven by BlockPool block tables.

    With ``async_swap`` (default) the host crossings run on a background
    :class:`SwapStream`: ``swap_out`` gathers the private pages into a
    device-side staging snapshot (in dispatch order — safe against this
    very tick re-leasing the ids) and hands the D2H drain to the worker;
    ``prefetch_swap_in`` uploads the host copy to standalone device buffers
    ahead of the restore, so ``swap_in`` only pays a device-side scatter.
    """

    def __init__(self, backend: JaxBackend, total_pages: int, page: int,
                 async_swap: bool = True, disk_spool: Optional[str] = None):
        self.b = backend
        self.page = page
        self.total_pages = total_pages
        # NVMe spill data plane (kvcache.disk_tier.DiskFileStore), created
        # lazily on the first spill so host-only runs never touch disk
        self._spool_dir = disk_spool
        self._filestore: Optional[DiskFileStore] = None
        self.binding = DeviceBindingMap(total_pages)
        self.scratch = self.binding.scratch_page
        cfg, dtype = backend.cfg, backend.dtype
        self.cache = PagedKVCache.zeros(cfg, total_pages + 1, page, dtype)
        # host copies of offloaded private blocks:
        # sid -> (k (L, n, page, Hkv, D), v (...)) in swap-record order
        self._host: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self.stream: Optional[SwapStream] = (SwapStream(n_buffers=2)
                                             if async_swap else None)
        # async state, all guarded by _mu: in-flight D2H futures (drained
        # by a same-tick swap_in), prefetched device buffers, and the sids
        # whose host state was dropped while a transfer was in flight (the
        # straggler job must not resurrect them). FIFO on the stream keeps
        # a drop -> re-offload sequence correct: the stale drain lands
        # before the fresh one.
        self._mu = threading.Lock()
        self._d2h: Dict[int, TransferFuture] = {}
        self._prefetch: Dict[int, Tuple[jax.Array, jax.Array]] = {}
        self._dropped: set = set()

        def _decode(params, cache, tokens, positions, tables, lengths,
                    wpid, woff):
            logits, cache = lm_decode_paged(cfg, params, cache, tokens,
                                            positions, tables, lengths,
                                            wpid, woff)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        def _prefill(params, cache, tokens, positions, table, wpid, woff,
                     kv_len, last_idx):
            hidden, cache = lm_prefill_paged(cfg, params, cache, tokens,
                                             positions, table, wpid, woff,
                                             kv_len, return_hidden=True)
            return prefill_next_token(cfg, params, hidden, last_idx), cache

        def _copy_page(cache, src, dst):
            return PagedKVCache(cache.k.at[:, dst].set(cache.k[:, src]),
                                cache.v.at[:, dst].set(cache.v[:, src]))

        def _mixed(params, cache, p_toks, p_pos, p_tables, p_wpid, p_woff,
                   p_kvlen, p_last, d_toks, d_pos, d_tables, d_lens,
                   d_wpid, d_woff):
            return lm_mixed_paged(cfg, params, cache, p_toks, p_pos,
                                  p_tables, p_wpid, p_woff, p_kvlen, p_last,
                                  d_toks, d_pos, d_tables, d_lens, d_wpid,
                                  d_woff)

        self._decode_fn = jax.jit(_decode, donate_argnums=(1,))
        self._prefill_fn = jax.jit(_prefill, donate_argnums=(1,))
        self._copy_fn = jax.jit(_copy_page, donate_argnums=(0,))
        self._mixed_fn = jax.jit(_mixed, donate_argnums=(1,))

    # --- binding / oracle -------------------------------------------------
    def bind_kv_pool(self, pool) -> None:
        assert pool.block_size == self.page, \
            f"pool block_size {pool.block_size} != page {self.page}"
        assert pool.total <= self.total_pages, \
            f"pool of {pool.total} blocks exceeds {self.total_pages} pages"

    def kv_bytes_per_token(self) -> float:
        k = self.cache.k             # (L, P, page, Hkv, D)
        per_tok = 2 * k.size // (k.shape[1] * k.shape[2]) * k.dtype.itemsize
        return float(per_tok)

    def calibrate(self) -> None:
        b = self.b
        C = 64
        toks = np.zeros((1, C), np.int32)
        pos = np.arange(C, dtype=np.int32)[None]
        Np = _bucket(C // self.page + 1, lo=2)
        table = np.full((Np,), self.scratch, np.int32)
        wpid = np.full((C,), self.scratch, np.int32)
        woff = np.arange(C, dtype=np.int32) % self.page

        def pf():
            nxt, self.cache = self._prefill_fn(
                b.params, self.cache, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(table), jnp.asarray(wpid), jnp.asarray(woff),
                jnp.asarray(C, jnp.int32), C - 1)
            nxt.block_until_ready()

        b._prefill_s_per_tok = b._time_once(pf) / C
        B = _bucket(b.max_slots, lo=1)
        tok1 = np.zeros((B,), np.int32)
        pos1 = np.zeros((B,), np.int32)
        tables = np.full((B, 2), self.scratch, np.int32)
        lens = np.ones((B,), np.int32)
        wp = np.full((B,), self.scratch, np.int32)
        wo = np.zeros((B,), np.int32)

        def df():
            nxt, self.cache = self._decode_fn(
                b.params, self.cache, jnp.asarray(tok1), jnp.asarray(pos1),
                jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(wp),
                jnp.asarray(wo))
            nxt.block_until_ready()

        b._decode_s_per_step = b._time_once(df)
        page_bytes = 2 * self.cache.k[:, 0].size * self.cache.k.dtype.itemsize

        def xfer():
            host = (jax.device_get(self.cache.k[:, 0]),
                    jax.device_get(self.cache.v[:, 0]))
            dev = (jax.device_put(host[0]), jax.device_put(host[1]))
            dev[0].block_until_ready()
            dev[1].block_until_ready()

        # round trip moves page_bytes each way; swap_time charges one
        # direction per call, so price it at the two-direction average
        b._h2d_bw = max(1e6, 2 * page_bytes / b._time_once(xfer))

    # --- session / host state ---------------------------------------------
    def release_session(self, sid: int) -> None:
        pass                         # placement is the engine's lease state

    def drop_host(self, sid: int) -> None:
        with self._mu:
            self._host.pop(sid, None)
            self._prefetch.pop(sid, None)
            self._d2h.pop(sid, None)
            if self.stream is not None:
                self._dropped.add(sid)   # in-flight jobs must not resurrect
        if self._filestore is not None:
            self._filestore.delete(sid)

    def close(self) -> None:
        if self.stream is not None:
            self.stream.close()
        if self._filestore is not None:
            self._filestore.close()
            self._filestore = None

    # --- NVMe spill/fill (TieredStore data plane) -------------------------
    def _store(self) -> DiskFileStore:
        if self._filestore is None:
            self._filestore = DiskFileStore(self._spool_dir)
        return self._filestore

    def spill_host(self, sid: int) -> Optional[TransferFuture]:
        """Write ``sid``'s host KV copy to the spool and free the DRAM
        copy. Submitted on the stream when one runs (FIFO: a demotion
        chained behind this tick's D2H drain lands after the bytes do);
        synchronous otherwise. Empty records (nothing private crossed
        PCIe) keep their (None, None) marker in DRAM — there is nothing
        to free and the restore path expects the marker."""
        store = self._store()

        def write() -> bool:
            with self._mu:
                if self.stream is not None and sid in self._dropped:
                    return False
                host = self._host.get(sid)
            if host is None or host[0] is None:
                return False           # nothing private: marker stays
            store.write(sid, host[0], host[1])
            with self._mu:
                if self.stream is not None and sid in self._dropped:
                    store.delete(sid)  # raced a detach: no resurrection
                    return False
                self._host.pop(sid, None)
            return True

        if self.stream is None:
            write()
            return None
        return self.stream.submit(write, sid=sid, direction="h2n")

    def fill_host(self, sid: int) -> Optional[TransferFuture]:
        """Read ``sid``'s spool file back into the host copy (promotion
        first hop); the engine's normal prefetch/swap-in path then moves
        it over PCIe."""
        store = self._store()

        def read() -> bool:
            data = store.read(sid)
            if data is None:
                return False           # empty record: marker never spilled
            with self._mu:
                if self.stream is not None and sid in self._dropped:
                    store.delete(sid)
                    return False
                self._host[sid] = data
            store.delete(sid)
            return True

        if self.stream is None:
            read()
            return None
        return self.stream.submit(read, sid=sid, direction="n2h")

    # --- swap: per-block host offload -------------------------------------
    def swap_out(self, s: Session) -> Optional[TransferFuture]:
        """D2H-copy only the blocks flagged private in the engine's swap
        record; shared/indexed prefix blocks stay resident on device. With
        the stream, the page gather (a device-side snapshot, ordered by
        dispatch before any later cache writes) happens here and the host
        crossing drains on the worker; the returned future joins the
        engine's swap-completion handshake."""
        rec = s.meta.get("swap_pages")
        if rec is None:
            return None
        sid = s.sid
        pids = [self.binding.page_of(bid) for bid, _gen, private in rec
                if private]
        if not pids:
            if self.stream is None:
                self._host[sid] = (None, None)
                return None

            def mark_empty():
                # through the FIFO, not inline: a stale drain for this sid
                # still queued from a dropped earlier offload must land
                # (and be discarded) before the guard is disarmed
                with self._mu:
                    self._dropped.discard(sid)
                    self._host[sid] = (None, None)

            return self.stream.submit(mark_empty, sid=sid, direction="d2h")
        # pad the gather to a power-of-two page count with the scratch page
        # (whose content is garbage by design): swap records grow a little
        # every round, and an unbucketed gather/scatter would XLA-compile a
        # fresh shape per round — in the tick, on the critical path
        idx = self._swap_index(pids)
        if self.stream is None:
            self._host[sid] = (jax.device_get(self.cache.k[:, idx]),
                               jax.device_get(self.cache.v[:, idx]))
            return None
        slot = self.stream.staging.acquire()     # double-buffer backpressure
        k_snap = self.cache.k[:, idx]            # device-side staging gather
        v_snap = self.cache.v[:, idx]
        with self._mu:
            self._dropped.discard(sid)

        def drain():
            try:
                host = (np.asarray(k_snap), np.asarray(v_snap))
                with self._mu:
                    if sid not in self._dropped:
                        self._host[sid] = host
                return host
            finally:
                self.stream.staging.release(slot)

        fut = self.stream.submit(drain, sid=sid, direction="d2h")
        with self._mu:
            self._d2h[sid] = fut
        return fut

    def prefetch_swap_in(self, sid: int) -> Optional[TransferFuture]:
        """Upload ``sid``'s private host blocks to standalone device
        buffers on the worker; the later ``swap_in`` then scatters them
        into the freshly leased pages device-side. Only callable once the
        D2H drain resolved (``HostTier.ready`` gates the engine)."""
        with self._mu:
            host = self._host.get(sid)
        if self.stream is None or host is None or host[0] is None:
            return None
        # slot acquired on the submitting thread (both directions): every
        # slot holder is then a job already in the FIFO ahead of any
        # waiter, so the worker never blocks on a slot it must itself free
        slot = self.stream.staging.acquire()

        def upload():
            try:
                dk = jax.device_put(host[0])
                dv = jax.device_put(host[1])
                dk.block_until_ready()
                dv.block_until_ready()
                with self._mu:
                    if sid not in self._dropped:
                        self._prefetch[sid] = (dk, dv)
                return (dk, dv)
            finally:
                self.stream.staging.release(slot)

        return self.stream.submit(upload, sid=sid, direction="h2d")

    def swap_in(self, s: Session, lease) -> None:
        """Restore private blocks into the freshly allocated pages at
        ``meta["restore_positions"]``; reacquired shared blocks need no
        transfer — their pages were never rewritten (gen-certified). A
        prefetched restore scatters device-resident buffers; otherwise the
        H2D upload happens inline (after waiting out a same-tick D2H)."""
        sid = s.sid
        with self._mu:
            d2h = self._d2h.pop(sid, None)
        if d2h is not None and not d2h.done():
            d2h.result()      # same-tick out->in: restore behind the drain
        with self._mu:
            pre = self._prefetch.pop(sid, None)
            host = self._host.pop(sid, None)
        if host is None or host[0] is None:
            return
        restore = s.meta.get("restore_positions", [])
        pids = [self.binding.page_of(lease[i]) for i in restore]
        assert _bucket(len(pids), lo=2) == host[0].shape[1], \
            f"restore mismatch: {len(pids)} pages, {host[0].shape[1]} copies"
        # scatter through the same scratch-padded bucket the drain gathered
        # (pad lanes dump their garbage back onto the scratch page)
        idx = self._swap_index(pids)
        dk, dv = pre if pre is not None else (jnp.asarray(host[0]),
                                              jnp.asarray(host[1]))
        self.cache = PagedKVCache(self.cache.k.at[:, idx].set(dk),
                                  self.cache.v.at[:, idx].set(dv))

    def _swap_index(self, pids: List[int]) -> np.ndarray:
        """Swap gather/scatter page index, padded to a power-of-two width
        with the scratch page so the eager ops compile O(log) shapes."""
        out = np.full((_bucket(len(pids), lo=2),), self.scratch, np.int32)
        out[:len(pids)] = pids
        return out

    def apply_cow(self, copies) -> None:
        """Mirror the tick's copy-on-write events as device page copies, in
        log order (a later copy may source a page an earlier one freed)."""
        for _sid, src, dst in copies:
            self.cache = self._copy_fn(self.cache,
                                       self.binding.page_of(src),
                                       self.binding.page_of(dst))

    # --- compute ----------------------------------------------------------
    def prefill(self, s: Session, chunk: int, lease) -> None:
        b, page = self.b, self.page
        ids = b._context_ids(s)
        start = s.resident_len
        segment = ids[start:start + chunk]
        C = _bucket(len(segment))
        # gathered view must cover the lease and end in a scratch page (the
        # padded lanes' parking position)
        n_need = max(len(lease), -(-(start + C) // page)) + 1
        Np = _bucket(n_need, lo=2)
        table = self.binding.table(lease, width=Np)
        toks = np.zeros((1, C), np.int32)
        toks[0, :len(segment)] = segment
        pos = np.full((C,), Np * page - 1, np.int32)
        pos[:len(segment)] = np.arange(start, start + len(segment))
        wpid = np.full((C,), self.scratch, np.int32)
        woff = np.zeros((C,), np.int32)
        for i in range(len(segment)):
            wpid[i] = self.binding.page_of(lease[(start + i) // page])
            woff[i] = (start + i) % page
        nxt, self.cache = self._prefill_fn(
            b.params, self.cache, jnp.asarray(toks), jnp.asarray(pos[None]),
            jnp.asarray(table), jnp.asarray(wpid), jnp.asarray(woff),
            jnp.asarray(start + len(segment), jnp.int32), len(segment) - 1)
        s.meta["next_token"] = int(nxt)
        # analytic HBM bytes-touched accounting for this chunk (surfaced as
        # dispatch_stats counters -> metrics probe / Perfetto counter track
        # / bench figure): the legacy gather path pays 3x the gathered view
        # (gather read + dense-copy write + attention read) plus ~3x the
        # chunk (dense write, slice, scatter); the in-place path pays the
        # view once (attention read) plus the chunk scatter
        tok_bytes = self.kv_bytes_per_token()
        ctx_toks, chunk_toks = Np * page, C
        st = b.dispatch_stats
        st["prefill_gather_bytes"] += \
            (3 * ctx_toks + 3 * chunk_toks) * tok_bytes
        st["prefill_inplace_bytes"] += (ctx_toks + chunk_toks) * tok_bytes

    def decodes(self, decodes, leases) -> None:
        b, page = self.b, self.page
        live = [(s, leases[s.sid], g) for s, g in decodes]
        B = _bucket(len(live), lo=1)
        maxp = _bucket(max(len(l) for _, l, _ in live), lo=1)
        tables = np.full((B, maxp), self.scratch, np.int32)
        for i, (_s, lease, _g) in enumerate(live):
            tables[i, :len(lease)] = [self.binding.page_of(x) for x in lease]
        g_max = max(g for _, _, g in live)
        jtables = jnp.asarray(tables)
        for step in range(g_max):
            toks = np.zeros((B,), np.int32)
            pos = np.zeros((B,), np.int32)
            lens = np.ones((B,), np.int32)
            wpid = np.full((B,), self.scratch, np.int32)
            woff = np.zeros((B,), np.int32)
            active: List[Tuple[Session, int]] = []
            for i, (s, lease, g) in enumerate(live):
                if step >= g:
                    continue
                p = s.resident_len + step
                toks[i] = s.meta.get("next_token", 1)
                pos[i] = p
                lens[i] = p + 1
                wpid[i] = self.binding.page_of(lease[p // page])
                woff[i] = p % page
                active.append((s, i))
            if not active:
                break
            nxt, self.cache = self._decode_fn(
                b.params, self.cache, jnp.asarray(toks), jnp.asarray(pos),
                jtables, jnp.asarray(lens), jnp.asarray(wpid),
                jnp.asarray(woff))
            nxt = np.asarray(nxt)
            for s, i in active:
                tok = int(nxt[i])
                s.meta.setdefault("generated", []).append(tok)
                s.meta["next_token"] = tok
                s.meta.setdefault("context_ids", []).append(tok)

    # --- fused mixed iteration --------------------------------------------
    def run_mixed(self, work: BatchWork) -> None:
        """One iteration-level tick as a SINGLE jitted dispatch: every
        prefill chunk becomes a pack of the scanned prefill stage and every
        decode lane advances one token, over one shared cache round-trip —
        the per-session prefill dispatches and the sequential decode-step
        loop of the round path collapse into one ``lm_mixed_paged`` call.
        Pack shape (C, Np), lane count B and pack count P are all bucketed
        to powers of two; slack packs/lanes park on the scratch page (the
        same construction ``calibrate`` warms)."""
        b, page = self.b, self.page
        leases = work.leases
        packs = []
        for s, chunk in work.prefills:
            ids = b._context_ids(s)
            start = s.resident_len
            packs.append((s, ids[start:start + chunk], start,
                          leases.get(s.sid, ())))
        assert all(g == 1 for _, g in work.decodes), \
            "mixed tick: decode lanes carry exactly one token"
        decodes = [(s, leases[s.sid]) for s, _g in work.decodes]

        C = _bucket(max((len(seg) for _, seg, _, _ in packs), default=1))
        n_need = 2
        for _, seg, start, lease in packs:
            n_need = max(n_need,
                         max(len(lease), -(-(start + C) // page)) + 1)
        Np = _bucket(n_need, lo=2)
        P = _bucket(len(packs), lo=1) if packs else 0
        # slack packs mirror the calibrate construction: all-scratch table,
        # full-C scratch write, kv_len C — garbage in, garbage discarded
        p_toks = np.zeros((P, 1, C), np.int32)
        p_pos = np.full((P, 1, C), Np * page - 1, np.int32)
        p_tables = np.full((P, Np), self.scratch, np.int32)
        p_wpid = np.full((P, C), self.scratch, np.int32)
        p_woff = np.tile(np.arange(C, dtype=np.int32) % page, (P, 1))
        p_kvlen = np.full((P,), C, np.int32)
        p_last = np.full((P,), C - 1, np.int32)
        for j, (s, seg, start, lease) in enumerate(packs):
            p_toks[j, 0, :len(seg)] = seg
            p_pos[j, 0, :len(seg)] = np.arange(start, start + len(seg))
            p_tables[j] = self.binding.table(lease, width=Np)
            p_woff[j] = 0
            for i in range(len(seg)):
                p_wpid[j, i] = self.binding.page_of(lease[(start + i) // page])
                p_woff[j, i] = (start + i) % page
            p_kvlen[j] = start + len(seg)
            p_last[j] = len(seg) - 1

        B = _bucket(len(decodes), lo=1) if decodes else 0
        maxp = _bucket(max((len(l) for _, l in decodes), default=1), lo=1)
        d_toks = np.zeros((B,), np.int32)
        d_pos = np.zeros((B,), np.int32)
        d_tables = np.full((B, maxp), self.scratch, np.int32)
        d_lens = np.ones((B,), np.int32)
        d_wpid = np.full((B,), self.scratch, np.int32)
        d_woff = np.zeros((B,), np.int32)
        for i, (s, lease) in enumerate(decodes):
            p = s.resident_len
            d_tables[i, :len(lease)] = [self.binding.page_of(x)
                                        for x in lease]
            d_toks[i] = s.meta.get("next_token", 1)
            d_pos[i] = p
            d_lens[i] = p + 1
            d_wpid[i] = self.binding.page_of(lease[p // page])
            d_woff[i] = p % page

        p_next, d_next, self.cache = self._mixed_fn(
            b.params, self.cache, jnp.asarray(p_toks), jnp.asarray(p_pos),
            jnp.asarray(p_tables), jnp.asarray(p_wpid), jnp.asarray(p_woff),
            jnp.asarray(p_kvlen), jnp.asarray(p_last), jnp.asarray(d_toks),
            jnp.asarray(d_pos), jnp.asarray(d_tables), jnp.asarray(d_lens),
            jnp.asarray(d_wpid), jnp.asarray(d_woff))
        if packs:
            p_next = np.asarray(p_next)
            for j, (s, _seg, _start, _lease) in enumerate(packs):
                s.meta["next_token"] = int(p_next[j])
        if decodes:
            d_next = np.asarray(d_next)
            for i, (s, _lease) in enumerate(decodes):
                tok = int(d_next[i])
                s.meta.setdefault("generated", []).append(tok)
                s.meta["next_token"] = tok
                s.meta.setdefault("context_ids", []).append(tok)
        # per-chunk analytic HBM accounting, same model as prefill()
        tok_bytes = self.kv_bytes_per_token()
        st = b.dispatch_stats
        for _ in packs:
            st["prefill_gather_bytes"] += \
                (3 * Np * page + 3 * C) * tok_bytes
            st["prefill_inplace_bytes"] += (Np * page + C) * tok_bytes


class _DenseLayout(_CacheLayout):
    """Slot-dense legacy layout: R fixed slots of ``max_len`` dense tokens.

    Position ``max_len - 1`` of every slot is scratch: idle decode lanes
    park their writes there, so sessions may use at most ``max_len - 1``
    tokens. Host offload copies whole slots (no block granularity)."""

    def __init__(self, backend: JaxBackend):
        self.b = backend
        cfg, dtype = backend.cfg, backend.dtype
        self.cache = model_zoo.cache_zeros(cfg, backend.max_slots,
                                           backend.max_len, dtype)
        self._slots: Dict[int, int] = {}          # sid -> slot
        self._free_slots = list(range(backend.max_slots))
        self._host_kv: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

        def _decode(params, cache, tokens, positions):
            logits, cache = lm_step(cfg, params, cache, tokens[:, None],
                                    positions[:, None])
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return nxt, cache

        def _prefill(params, cache, tokens, positions, slot, last_idx):
            # single-slot chunked prefill: slice the slot's cache region,
            # step, write back. tokens/positions: (1, C).
            ks = jax.lax.dynamic_slice_in_dim(cache.k, slot, 1, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(cache.v, slot, 1, axis=1)
            logits, sub = lm_step(cfg, params, KVCache(ks, vs), tokens,
                                  positions)
            k = jax.lax.dynamic_update_slice_in_dim(cache.k, sub.k, slot,
                                                    axis=1)
            v = jax.lax.dynamic_update_slice_in_dim(cache.v, sub.v, slot,
                                                    axis=1)
            nxt = jnp.argmax(logits[0, last_idx], axis=-1).astype(jnp.int32)
            return nxt, KVCache(k, v)

        self._decode_fn = jax.jit(_decode, donate_argnums=(1,))
        self._prefill_fn = jax.jit(_prefill, donate_argnums=(1,))

    # --- oracle -----------------------------------------------------------
    def kv_bytes_per_token(self) -> float:
        k = self.cache.k
        # (L, S, T, H, D) slot-dense layout: bytes/token = all-but-T dims
        per_tok = 2 * k.size // (k.shape[1] * k.shape[2]) * k.dtype.itemsize
        return float(per_tok)

    def calibrate(self) -> None:
        b = self.b
        toks = jnp.zeros((1, 64), jnp.int32)
        pos = jnp.arange(64, dtype=jnp.int32)[None]

        def pf():
            nxt, self.cache = self._prefill_fn(b.params, self.cache, toks,
                                               pos, 0, 63)
            nxt.block_until_ready()

        b._prefill_s_per_tok = b._time_once(pf) / 64
        tok1 = jnp.zeros((b.max_slots,), jnp.int32)
        pos1 = jnp.full((b.max_slots,), b.max_len - 1, jnp.int32)

        def df():
            nxt, self.cache = self._decode_fn(b.params, self.cache, tok1,
                                              pos1)
            nxt.block_until_ready()

        b._decode_s_per_step = b._time_once(df)
        slot_bytes = 2 * self.cache.k[:, 0].size * self.cache.k.dtype.itemsize

        def xfer():
            host = (jax.device_get(self.cache.k[:, 0]),
                    jax.device_get(self.cache.v[:, 0]))
            dev = (jax.device_put(host[0]), jax.device_put(host[1]))
            dev[0].block_until_ready()
            dev[1].block_until_ready()

        b._h2d_bw = max(1e6, 2 * slot_bytes / b._time_once(xfer))

    # --- slots ------------------------------------------------------------
    def _slot_of(self, sid: int) -> int:
        if sid not in self._slots:
            assert self._free_slots, "live runner out of slots"
            self._slots[sid] = self._free_slots.pop()
        return self._slots[sid]

    def release_session(self, sid: int) -> None:
        slot = self._slots.pop(sid, None)
        if slot is not None:
            self._free_slots.append(slot)

    def drop_host(self, sid: int) -> None:
        self._host_kv.pop(sid, None)

    # --- whole-slot host offload ------------------------------------------
    def swap_out(self, s: Session) -> None:
        slot = self._slots.get(s.sid)
        if slot is None:
            return
        self._host_kv[s.sid] = (jax.device_get(self.cache.k[:, slot]),
                                jax.device_get(self.cache.v[:, slot]))
        self.release_session(s.sid)

    def swap_in(self, s: Session, lease) -> None:
        host = self._host_kv.pop(s.sid, None)
        if host is None:
            return
        slot = self._slot_of(s.sid)
        k = self.cache.k.at[:, slot].set(jnp.asarray(host[0]))
        v = self.cache.v.at[:, slot].set(jnp.asarray(host[1]))
        self.cache = KVCache(k, v)

    def apply_cow(self, copies) -> None:
        pass                  # no physical sharing: nothing aliases a slot

    # --- compute ----------------------------------------------------------
    def prefill(self, s: Session, chunk: int, lease) -> None:
        b = self.b
        slot = self._slot_of(s.sid)
        ids = b._context_ids(s)
        start = s.resident_len
        segment = ids[start:start + chunk]
        bk = _bucket(len(segment))
        toks = np.zeros((1, bk), np.int32)
        toks[0, :len(segment)] = segment
        pos = np.arange(start, start + bk, dtype=np.int32)
        # padded lanes park at the scratch position
        pos[len(segment):] = b.max_len - 1
        nxt, self.cache = self._prefill_fn(
            b.params, self.cache, jnp.asarray(toks),
            jnp.asarray(pos[None]), slot, len(segment) - 1)
        s.meta["next_token"] = int(nxt)

    def decodes(self, decodes, leases) -> None:
        b = self.b
        g_max = max(g for _, g in decodes)
        scratch = b.max_len - 1
        for step in range(g_max):
            toks = np.zeros((b.max_slots,), np.int32)
            pos = np.full((b.max_slots,), scratch, np.int32)
            live = []
            for s, g in decodes:
                if step >= g:
                    continue
                slot = self._slot_of(s.sid)
                toks[slot] = s.meta.get("next_token", 1)
                pos[slot] = s.resident_len + step
                live.append((s, slot))
            if not live:
                break
            nxt, self.cache = self._decode_fn(
                b.params, self.cache, jnp.asarray(toks), jnp.asarray(pos))
            nxt = np.asarray(nxt)
            for s, slot in live:
                tok = int(nxt[slot])
                s.meta.setdefault("generated", []).append(tok)
                s.meta["next_token"] = tok
                s.meta.setdefault("context_ids", []).append(tok)
