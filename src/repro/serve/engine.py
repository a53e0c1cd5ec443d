"""Paged continuous-batching serve engine.

One jit-compiled, shape-stable decode step serves every phase and
occupancy: ``(params, caches, tokens(S,), lengths(S,), active(S,),
page_tables(S,P)) -> (next_tokens(S,), caches')`` with the cache buffers
donated.  The pool is lane-dense and the layer loop carries it: each layer
writes its S new rows in place and the paged kernel reads the layer's
pages from the pool as stored, so a step moves the rows it writes and the
pages it reads, not the pool (compiled for a v5e at serve-chat's size:
the step aliases exactly the pool's bytes and allocates no pool-sized
temporary; ``tests/test_tpu_compile.py``).
Prefill is by decode — the scheduler feeds prompt tokens one per step —
so there is exactly one executable, compiled once.

The per-unit math mirrors ``model.decode_step`` + ``attention.decode_gqa``
operation for operation (same ``_qkv``/rope/mask/``grouped_attend``/
``apply_ffn_unit`` calls on the ref backend), which is what makes the
paged ≡ dense greedy-token equivalence gate bitwise on matching shapes
(``max_pages_per_seq * page_size == s_max``).
"""
from __future__ import annotations

import contextlib
import math
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import model as M
from repro.models.layers import apply_norm, apply_rope
from repro.serve import attention_paged as pa
from repro.serve.pages import PageManager
from repro.serve.scheduler import Request, Scheduler


def supports_paged(cfg: ModelConfig) -> Tuple[bool, str]:
    """Which architectures the paged engine serves. SSM/hybrid state is
    recurrent (nothing to page); MLA's latent cache and the enc-dec/mrope
    position machinery are follow-ups (serve/README.md)."""
    if cfg.family not in ("dense", "moe"):
        return False, f"family {cfg.family!r}: only dense/moe attention " \
                      f"stacks have a pageable KV cache"
    if cfg.attn_type != "gqa":
        return False, "mla latent cache is not paged yet"
    if cfg.is_encdec or cfg.modality != "text":
        return False, "enc-dec / multimodal prefill is not paged yet"
    if cfg.mrope:
        return False, "mrope positions are not paged yet"
    return True, ""


def init_kv_pages(cfg: ModelConfig, *, n_pages: int, page_size: int,
                  dtype=None) -> List[Dict[str, jnp.ndarray]]:
    """Per-segment paged KV stores ``(n_units, 1 + n_pages, ps, KV * dh)``,
    lane-dense: a page row holds the keys (or values) of every KV head.
    Index 0 along the page dim is the scratch page (PageManager contract);
    one physical page id addresses the same slot in every unit's store."""
    dt = jnp.dtype(dtype or cfg.param_dtype)
    shape = (n_pages + 1, page_size, cfg.n_kv_heads * cfg.resolved_head_dim)
    return [{"k": jnp.zeros((s.n,) + shape, dt),
             "v": jnp.zeros((s.n,) + shape, dt)}
            for s in M.build_segments(cfg)]


def kv_pool_bytes(cfg: ModelConfig, *, n_pages: int, page_size: int,
                  dtype=None) -> int:
    """Bytes of the K and V stores ``init_kv_pages`` makes for ``n_pages``
    pages: the pages and the scratch page, with no padding."""
    dt = jnp.dtype(dtype or cfg.param_dtype)
    n_units = sum(s.n for s in M.build_segments(cfg))
    return (n_units * (1 + n_pages) * page_size * cfg.n_kv_heads
            * cfg.resolved_head_dim * 2 * dt.itemsize)


def dense_kv_bytes(cfg: ModelConfig, *, n_seqs: int, s_max: int,
                   dtype=None) -> int:
    """What the dense serving loop keeps resident for the same concurrency:
    every sequence owns a full (s_max, KV, dh) strip per unit for its whole
    lifetime, whether it uses it or not."""
    dt = jnp.dtype(dtype or cfg.param_dtype)
    n_units = sum(s.n for s in M.build_segments(cfg))
    return (n_units * n_seqs * s_max * cfg.n_kv_heads
            * cfg.resolved_head_dim * 2 * dt.itemsize)


def segment_windows(cfg: ModelConfig) -> List[Tuple[int, int]]:
    """``(units, attention window)`` of each segment: local segments
    attend over ``cfg.sliding_window`` positions, the others (0) over all
    cached ones."""
    return [(s.n, cfg.sliding_window if s.kind == "local" else 0)
            for s in M.build_segments(cfg)]


def make_paged_decode_step(cfg: ModelConfig, *, backend: str = "ref",
                           return_logits: bool = False):
    """The engine's device step: one token per slot through every layer,
    writing its K/V into the pages.  Returns ``(next_tokens, caches)``,
    and the step's logits ``(max_seqs, vocab)`` last when
    ``return_logits``.

    The layer scan carries each segment's pool with the activations and
    takes each layer's weights with its index: ``write_kv`` updates the
    carried pool at that layer and the kernel reads it there, so the
    donated pool is the loop's buffer from the first layer to the output.

    Regions: ``decode.layers`` (the layer scan, whose own work is slicing
    the stacked weights), inside it ``model.attn`` with
    ``decode.kv_write`` and ``decode.paged_attention``, and ``model.ffn``;
    ``model.embed`` and ``model.head`` around it."""
    ok, why = supports_paged(cfg)
    if not ok:
        raise NotImplementedError(why)
    hd = cfg.resolved_head_dim
    segs = M.build_segments(cfg)
    windows = [w for _, w in segment_windows(cfg)]

    def unit_step(p, layer, x1, cache, lengths, active, page_tables, *,
                  window: int, use_moe: bool):
        # mirrors model decode_unit / attention.decode_gqa op-for-op
        with jax.named_scope("model.attn"):
            h = apply_norm(p["ln1"], x1, cfg.norm)
            q, k_new, v_new = attn._qkv(p["attn"], h, cfg.n_heads,
                                        cfg.n_kv_heads, hd)
            pos = lengths[:, None]
            q = apply_rope(q, pos, cfg.rope_theta)
            k_new = apply_rope(k_new, pos, cfg.rope_theta)
            with jax.named_scope("decode.kv_write"):
                cache = pa.write_kv(cache, layer, k_new[:, 0],
                                    v_new[:, 0], page_tables, lengths,
                                    active)
            with jax.named_scope("decode.paged_attention"):
                o = pa.paged_attention(q, cache, layer, page_tables,
                                       lengths, window=window,
                                       backend=backend)
            a = o.reshape(x1.shape[0], 1, -1) @ p["attn"]["wo"]
        if cfg.parallel_residual and not use_moe:
            f, _ = M.apply_ffn_unit(p, x1, cfg, use_moe=use_moe)
            x1 = x1 + a + f
        else:
            x1 = x1 + a
            f, _ = M.apply_ffn_unit(p, x1, cfg, use_moe=use_moe)
            x1 = x1 + f
        return x1, cache

    def step(params, caches, tokens, lengths, active, page_tables):
        x1 = M.embed_tokens(params, cfg, tokens[:, None])
        x1 = M.shard_act(x1, "act")
        new_caches = []
        for s, window, sp, cache in zip(segs, windows, params["segments"],
                                        caches):
            use_moe = s.kind == "moe"

            def scan_fn(carry, pl_, _w=window, _m=use_moe):
                x1, c = carry
                p, layer = pl_
                return unit_step(p, layer, x1, c, lengths, active,
                                 page_tables, window=_w, use_moe=_m), None

            with jax.named_scope("decode.layers"):
                (x1, nc), _ = jax.lax.scan(
                    scan_fn, (x1, cache),
                    (sp, jnp.arange(s.n, dtype=jnp.int32)))
            new_caches.append(nc)
        with jax.named_scope("model.head"):
            logits = M.logits_fn(params, cfg, x1)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        if return_logits:
            return nxt, new_caches, logits[:, -1]
        return nxt, new_caches

    return step


def _pct(vals, q):
    if not vals:
        return float("nan")
    return float(np.percentile(np.asarray(vals, np.float64), q))


class ServeEngine:
    """Ties the page manager, scheduler, and jitted paged step together.

    ``eos_id`` defaults to ``cfg.eos_id``; pass ``None`` to disable EOS
    (equivalence tests / fixed-length load traces). ``step_fn`` lets
    callers share one jitted executable across engines (the benchmark's
    continuous-vs-static fairness: identical compiled step, only the
    admission policy differs).  ``keep_logits`` copies every step's logits
    to the host: ``self.logits[rid][pos]`` is the row that predicted
    token ``pos + 1`` of request ``rid`` (a check against a dense
    forward, at the cost of one device-to-host copy per step).
    """

    def __init__(self, params, cfg: ModelConfig, *, max_seqs: int,
                 page_size: int, n_pages: int, max_pages_per_seq: int,
                 backend: str = "ref", eos_id: Any = "cfg",
                 policy: str = "continuous", dtype=None, step_fn=None,
                 metrics=None, span=None, keep_logits: bool = False):
        ok, why = supports_paged(cfg)
        if not ok:
            raise NotImplementedError(f"{cfg.name}: {why}")
        self.params = params
        self.cfg = cfg
        self.page_size = int(page_size)
        self.n_pages = int(n_pages)
        self._dtype = dtype
        self.pages = PageManager(n_pages, page_size, max_seqs,
                                 max_pages_per_seq)
        self.sched = Scheduler(self.pages, max_seqs=max_seqs,
                               eos_id=(cfg.eos_id if eos_id == "cfg"
                                       else eos_id),
                               policy=policy)
        self.caches = init_kv_pages(cfg, n_pages=n_pages,
                                    page_size=page_size, dtype=dtype)
        self._fn = step_fn if step_fn is not None else jax.jit(
            make_paged_decode_step(cfg, backend=backend,
                                   return_logits=keep_logits),
            donate_argnums=(1,))
        self.logits = {} if keep_logits else None
        self.step_count = 0
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self._windows = segment_windows(cfg)
        self.kv_pages_read = 0
        self.kv_pages_grid = 0
        self._metrics = metrics
        self._span = (span if span is not None
                      else (lambda name, **kw: contextlib.nullcontext()))
        self._rid = 0

    # -- submission --------------------------------------------------------
    def submit(self, prompt, max_new: int, arrival: int = 0) -> Request:
        prompt = [int(t) for t in prompt]
        total = len(prompt) + int(max_new)
        cap = self.pages.max_pages_per_seq * self.page_size
        if total > cap:
            raise ValueError(f"request needs {total} tokens > "
                             f"max_pages_per_seq*page_size = {cap}")
        req = Request(rid=self._rid, prompt=prompt, max_new=int(max_new),
                      arrival=int(arrival))
        self._rid += 1
        self.sched.submit(req)
        return req

    def compile(self) -> Dict[str, Any]:
        """AOT-compile the decode step for this engine's shapes, so that
        the first ``step`` runs it instead of compiling it.  Returns the
        compile seconds and the executable's ``memory_analysis`` bytes."""
        S = self.pages.max_seqs
        zeros = jnp.zeros((S,), jnp.int32)
        t = time.perf_counter()
        self._fn = self._fn.lower(
            self.params, self.caches, zeros, zeros, jnp.zeros((S,), bool),
            jnp.asarray(self.pages.page_table)).compile()
        seconds = time.perf_counter() - t
        ma = self._fn.memory_analysis()
        return {"compile_s": seconds,
                "memory": {k: int(getattr(ma, k + "_size_in_bytes"))
                           for k in ("argument", "output", "alias",
                                     "temp")}}

    # -- stepping ----------------------------------------------------------
    def step(self) -> bool:
        """One engine iteration (admit -> plan -> device step -> commit).
        Returns False when there is nothing left to do.

        Spans (through ``span``): ``admit``, ``plan``, ``device_step``
        holding ``put`` (the inputs' copies to the device), ``dispatch``
        (the jitted call) and ``fetch`` (the next tokens back to the
        host), then ``commit``."""
        sched = self.sched
        if not sched.has_work():
            return False
        with self._span("admit"):
            sched.admit_ready(self.step_count, time.monotonic())
        with self._span("plan"):
            plan = sched.plan_step()
        if plan is None:
            # every remaining request arrives in the future: tick the clock
            self.step_count += 1
            return True
        tokens, lengths, active = plan
        with self._span("device_step", n_active=int(active.sum())):
            with self._span("put"):
                args = (jnp.asarray(tokens), jnp.asarray(lengths),
                        jnp.asarray(active),
                        jnp.asarray(self.pages.page_table))
            with self._span("dispatch"):
                nxt, self.caches, *logits = self._fn(
                    self.params, self.caches, *args)
            with self._span("fetch"):
                nxt = np.asarray(nxt)
        if self.logits is not None:
            rows = np.asarray(logits[0])
            for i in np.flatnonzero(active):
                self.logits.setdefault(sched.slots[i].req.rid, {})[
                    int(lengths[i])] = rows[i]
        n_prefill = sum(1 for s in sched.slots
                        if s is not None and s.fed < len(s.req.prompt) - 1)
        n_active = int(active.sum())
        self.prefill_tokens += n_prefill
        self.decode_tokens += n_active - n_prefill
        # the paged kernel's copies this step, over every layer: the
        # pages each active slot's length (and window) reaches, against
        # the whole table rows a fixed grid would walk
        pages_read = sum(
            n * int(pa.page_span(lengths[active], self.page_size, w)[1].sum())
            for n, w in self._windows)
        pages_grid = (sum(n for n, _ in self._windows) * n_active
                      * self.pages.max_pages_per_seq)
        self.kv_pages_read += pages_read
        self.kv_pages_grid += pages_grid
        with self._span("commit"):
            sched.commit(nxt, self.step_count, time.monotonic())
        if self._metrics is not None:
            m = self._metrics
            m.counter("repro_serve_steps").inc()
            m.counter("repro_serve_prefill_tokens").inc(n_prefill)
            m.counter("repro_serve_decode_tokens").inc(n_active - n_prefill)
            m.counter("repro_serve_kv_pages_read").inc(pages_read)
            m.counter("repro_serve_kv_pages_grid").inc(pages_grid)
            m.gauge("repro_serve_pages_in_use").set(self.pages.used_pages)
            m.gauge("repro_serve_waiting").set(len(sched.waiting))
        self.step_count += 1
        return True

    def run(self, max_steps: int = 100_000) -> Dict[str, Any]:
        t0 = time.monotonic()
        while self.step():
            if self.step_count >= max_steps:
                raise RuntimeError(f"engine did not drain in {max_steps} "
                                   f"steps")
        wall = time.monotonic() - t0
        return self.stats(wall)

    # -- reporting ---------------------------------------------------------
    def stats(self, wall_s: float) -> Dict[str, Any]:
        done = self.sched.done
        ttft_steps = [r.first_token_step - r.arrival for r in done
                      if r.first_token_step is not None]
        ttft_ms = [(r.first_token_wall - r.admit_wall) * 1e3 for r in done
                   if r.first_token_wall is not None]
        per_tok_ms = [(r.done_wall - r.first_token_wall) * 1e3
                      / max(1, len(r.generated) - 1) for r in done
                      if r.done_wall is not None and len(r.generated) > 1]
        steps = max(1, self.step_count)
        return {
            "requests_done": len(done),
            "steps": self.step_count,
            "wall_s": wall_s,
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "prefill_tok_s": self.prefill_tokens / max(wall_s, 1e-9),
            "decode_tok_s": self.decode_tokens / max(wall_s, 1e-9),
            # deterministic throughput: both policies run the identical
            # compiled step, so tokens-per-step ratios ARE tokens/s ratios
            "decode_tok_per_step": self.decode_tokens / steps,
            "kv_pages_read": self.kv_pages_read,
            "kv_pages_grid": self.kv_pages_grid,
            "ttft_steps_p50": _pct(ttft_steps, 50),
            "ttft_steps_p99": _pct(ttft_steps, 99),
            "ttft_ms_p50": _pct(ttft_ms, 50),
            "ttft_ms_p99": _pct(ttft_ms, 99),
            "per_token_ms_p50": _pct(per_tok_ms, 50),
            "per_token_ms_p99": _pct(per_tok_ms, 99),
            "admission_fingerprint": self.sched.admission_fingerprint(),
            "admission_deferrals": self.sched.deferred,
            "peak_pages_used": self.pages.peak_pages_used,
            "kv_pool_bytes": self.kv_pool_bytes(),
            "kv_peak_bytes": self.kv_resident_bytes(
                self.pages.peak_pages_used),
            "dense_equiv_bytes": self.dense_equiv_bytes(),
        }

    def kv_pool_bytes(self) -> int:
        return kv_pool_bytes(self.cfg, n_pages=self.n_pages,
                             page_size=self.page_size, dtype=self._dtype)

    def kv_resident_bytes(self, n_used: int) -> int:
        return kv_pool_bytes(self.cfg, n_pages=n_used,
                             page_size=self.page_size, dtype=self._dtype)

    def dense_equiv_bytes(self) -> int:
        return dense_kv_bytes(
            self.cfg, n_seqs=self.pages.max_seqs,
            s_max=self.pages.max_pages_per_seq * self.page_size,
            dtype=self._dtype)
