"""Prefill and decode runners — the two compute phases of serving, each
with its own phase-tagged plan-DB ladder.

Prefill is compute-bound (square-ish GEMMs over the whole prompt); decode
is bandwidth-bound (skinny M = lanes GEMMs).  The same logical GEMM spec
wants different schedules in each phase, so the runners wrap their
dispatches in ``search.serving_phase(...)``: while jit traces the step,
``ops._tuned_kernel`` sees the active phase and consults the
phase-qualified plan-DB entry first (falling back to the unphased one).
``sweep()`` populates those entries — the decode runner rewrites each
swept shape's M to its lane count, because that is the GEMM it actually
dispatches.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ... import obs, ops
from ...configs.base import ModelConfig
from ...models.api import ModelAPI
from ...obs import log
from ...search import serving_phase
from . import paged


def _sweep(phase: str, shapes, *, with_grads: bool, mesh_shape=None) -> int:
    from ...search import default_plan_db, search_gemm_plans

    db = default_plan_db()
    n = search_gemm_plans(
        shapes,
        dtype=jnp.bfloat16,
        plan_db=db,
        with_grads=with_grads,
        mesh_shape=mesh_shape,
        phase=phase,
    )
    log.info("serve", f"searched {n} {phase}-phase GEMM plan(s) -> {db.path}")
    return n


def _in_place() -> bool:
    """Whether the runners donate the pools, so that the decode step and
    the prefill store update them in place: on the chip's path, as ``ops``
    picks it.  Elsewhere each call returns new pools and leaves the
    caller's valid, so a CPU caller may hand a step's input pools back to
    the engine (the benchmark's rehearsal of a step that drops its KV
    writes does)."""
    return ops._use_pallas()


def _deq_fn(quant: Optional[str]):
    """Param expansion hook for the weight-only quant tier: identity when
    full-precision, ``optim.quant.dequantize_tree`` when serving ``--quant``
    — called INSIDE the jitted closures so the f32 weights are jit
    temporaries and only the 8-bit tree stays live."""
    if not quant:
        return lambda p: p
    from ...optim.quant import dequantize_tree

    return dequantize_tree


class PrefillRunner:
    """Batch-1 bucketed prefill: pads the context to a page multiple,
    masks the pads via ``lengths``, and copies the resulting cache pages
    into the physical pool.  Retraces once per padded-length bucket."""

    phase = "prefill"

    def __init__(self, cfg: ModelConfig, api: ModelAPI, page_size: int,
                 quant: Optional[str] = None):
        self.cfg = cfg
        self.page_size = page_size
        deq = _deq_fn(quant)

        def run(params, tokens, lengths):
            logits, caches = api.prefill(
                deq(params), cfg, {"tokens": tokens, "lengths": lengths},
                tokens.shape[1],
            )
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return tok, caches

        self._run = jax.jit(run)
        self._store = jax.jit(
            lambda pools, caches, page_ids: paged.store_prefill(
                pools, caches, page_ids, page_size
            ),
            donate_argnums=(0,) if _in_place() else (),
        )

    def sweep(self, shapes, *, with_grads: bool = True, mesh_shape=None):
        return _sweep(
            self.phase, shapes, with_grads=with_grads, mesh_shape=mesh_shape
        )

    def __call__(
        self, params, pools: Dict, context, pages
    ) -> Tuple[int, Dict]:
        """Prefill one request's context and store it into ``pages``.
        Returns (first generated token, updated pools)."""
        plen = len(context)
        padded = len(pages) * self.page_size
        assert padded >= plen
        toks = jnp.zeros((1, padded), jnp.int32)
        toks = toks.at[0, :plen].set(jnp.asarray(context, jnp.int32))
        lengths = jnp.full((1,), plen, jnp.int32)
        with serving_phase(self.phase):
            with obs.span("serve.prefill.dispatch"):
                tok, caches = self._run(params, toks, lengths)
                pools = self._store(
                    pools, caches, jnp.asarray(pages, jnp.int32)
                )
        # waits for the prefill only: the store runs on asynchronously
        with obs.span("serve.prefill.wait"):
            first = int(tok[0])
        return first, pools


class DecodeRunner:
    """One continuous-batching decode step over all lanes, straight from
    the pools: each layer reads its lanes' pages by block table, and the
    one KV row per lane and layer the step appends is written back to its
    page.  Fixed (lanes, max_pages) shapes — traced exactly once."""

    phase = "decode"

    def __init__(
        self, cfg: ModelConfig, api: ModelAPI, page_size: int,
        lanes: int, max_pages: int, quant: Optional[str] = None,
    ):
        self.cfg = cfg
        self.lanes = lanes
        self.max_pages = max_pages
        deq = _deq_fn(quant)

        def step(params, pools, block_table, lens, tokens):
            # the scopes name the paged-KV ops in the compiled step's
            # metadata, where a device trace's reader finds them: each
            # layer's page read is ``paged_view`` (models/layers.py)
            logits, rows = api.paged_decode_step(
                deq(params), cfg, pools, block_table, lens, tokens[:, None]
            )
            with jax.named_scope("scatter_token"):
                pools = paged.scatter_token(
                    pools, rows, block_table, lens, page_size
                )
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return tok, pools

        self._step = jax.jit(
            step, donate_argnums=(1,) if _in_place() else ()
        )

    def sweep(self, shapes, *, with_grads: bool = False, mesh_shape=None):
        # decode dispatches M = lanes activations regardless of what the
        # fleet swept for training/prefill — ladder the shapes it runs
        skinny = tuple((self.lanes, k, n) for (_, k, n) in shapes)
        return _sweep(
            self.phase, skinny, with_grads=with_grads, mesh_shape=mesh_shape
        )

    def __call__(self, params, pools, block_table, lens, tokens):
        """Returns (next_token per lane, updated pools)."""
        with serving_phase(self.phase):
            tok, pools = self._step(
                params, pools,
                jnp.asarray(block_table, jnp.int32),
                jnp.asarray(lens, jnp.int32),
                jnp.asarray(tokens, jnp.int32),
            )
        return tok, pools
