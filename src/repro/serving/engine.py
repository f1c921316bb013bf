"""Embedding runtime (paper §2.2 "offline remembering", Figure 6 left half).

Pipeline per drained queue batch:
  1. superficial pass — first N layers, one dense batch (cached per sample)
  2. pre-exit prediction — tiny MLP on the pooled superficial state
  3. exit-group batching — samples grouped by predicted exit; each group runs
     layers [N, e) as one dense, statically-shaped executable (compilation
     cached per (exit, batch-bucket))
  4. store — coarse embedding + INT4-quantized superficial activations into
     the EmbeddingStore (refinement fuel for §3.4)

Policies: "recall" (the above), "branchynet" (run layer-by-layer, exit on
confidence — no pre-exit, no batching), "fixed" (everyone exits at layer k),
"full" (no early exit). All share the same model fns so accuracy
comparisons are apples-to-apples; device-time comparisons for edge hardware
come from repro.core.scheduler's calibrated cost model.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MEMConfig, RecallConfig
from repro.core import preexit as PE
from repro.core import spans
from repro.core.scheduler import plan_exit_groups
from repro.core.store import EmbeddingStore
from repro.models import imagebind as IB
from repro.models import transformer as T


_unstack = jax.jit(jnp.unstack)  # (B, ...) -> B arrays, one dispatch


@dataclasses.dataclass
class EngineStats:
    n_embedded: int = 0
    layers_executed: float = 0.0
    group_batches: int = 0
    wall_s: float = 0.0

    @property
    def avg_layers(self) -> float:
        return self.layers_executed / max(self.n_embedded, 1)


class EmbeddingEngine:
    def __init__(self, params, cfg: MEMConfig, recall: RecallConfig, *,
                 modality: str = "vision", lora=None,
                 predictor_params=None, policy: str = "recall",
                 fixed_exit: Optional[int] = None, max_batch: int = 64,
                 store: Optional[EmbeddingStore] = None,
                 cache_activations: bool = True, fw_kw: Optional[dict] = None):
        self.params, self.cfg, self.recall = params, cfg, recall
        self.modality = modality
        self.lora = lora
        self.predictor = predictor_params
        self.policy = policy
        self.fixed_exit = fixed_exit
        self.max_batch = max_batch
        self.store = store if store is not None else EmbeddingStore(cfg.embed_dim)
        self.cache_activations = cache_activations
        self.fw_kw = fw_kw or {}
        self.tower = cfg.tower(modality)
        self.exits = recall.exit_layers(self.tower.n_layers)
        self.stats = EngineStats()
        self._queue: List[Tuple[int, np.ndarray]] = []

        # every jitted model fn takes (params, lora) as arguments: closed
        # over, the weights would be baked into each executable as constants
        # (GBs per compile at published widths)
        self._jit_superficial = jax.jit(self._superficial)
        self._jit_continue = {}  # (start, end) -> jitted fn

    # -- model fns -------------------------------------------------------------

    def _superficial(self, params, lora, x):
        """First-N-layer pass; returns hidden state + per-layer pooled states
        (exits at depth <= N read their embedding straight from these)."""
        N = self.recall.superficial_layers
        out = IB.tower_forward(params, self.cfg, self.recall, self.modality,
                               x, layer_end=N, lora=lora, **self.fw_kw)
        return out["h"], out["pooled"]  # (B,S,d), (N,B,d)

    def _continue_fn(self, start: int, end: int):
        """Jitted ``fn(params, lora, h)``: layers [start, end) from a
        cached hidden state, then the exit head."""
        key = (start, end)
        if key not in self._jit_continue:
            def fn(params, lora, h):
                out = IB.tower_forward(params, self.cfg, self.recall,
                                       self.modality, inputs=None, h_state=h,
                                       layer_start=start, layer_end=end,
                                       lora=lora, **self.fw_kw)
                tp = params["towers"][self.modality]
                return T.exit_embedding(tp, out["pooled"][-1],
                                        self.cfg.norm_eps)
            self._jit_continue[key] = jax.jit(fn)
        return self._jit_continue[key]

    # -- queue -------------------------------------------------------------------

    def submit(self, uid: int, item: np.ndarray) -> None:
        self._queue.append((uid, item))

    def submit_batch(self, uids: Sequence[int], items: np.ndarray) -> None:
        for u, it in zip(uids, items):
            self._queue.append((int(u), it))

    # -- execution ---------------------------------------------------------------

    def drain(self) -> EngineStats:
        """Embed everything queued; returns cumulative stats."""
        if not self._queue:
            return self.stats
        with spans.span("engine.drain") as sp:
            spans.count("items", len(self._queue))
            self._drain_queue()
        self.stats.wall_s += sp.s
        return self.stats

    def _drain_queue(self) -> None:
        with spans.span("engine.stack"):
            uids = np.array([u for u, _ in self._queue])
            items = np.stack([x for _, x in self._queue])
        self._queue.clear()
        N = self.recall.superficial_layers

        if self.policy == "full":
            pred_idx = np.full(len(uids), len(self.exits) - 1)
        elif self.policy == "fixed":
            fe = self.fixed_exit if self.fixed_exit is not None else self.exits[0]
            pred_idx = np.full(len(uids), self.exits.index(fe))
        elif self.policy in ("recall", "branchynet"):
            pred_idx = None  # decided below
        else:
            raise ValueError(self.policy)

        # 1) superficial pass (batched) — shared by every policy that needs
        # hidden states; branchynet also starts from layer 0 per sample.
        h_sup_parts, pooled_parts = [], []
        for i in range(0, len(items), self.max_batch):
            with spans.span("engine.superficial"):
                h, pooled = self._jit_superficial(
                    self.params, self.lora,
                    spans.to_device(items[i:i + self.max_batch]))
                h_sup_parts.append(spans.to_host(h))
                pooled_parts.append(spans.to_host(pooled))
        with spans.span("engine.concat"):
            h_sup = np.concatenate(h_sup_parts)
            pooled_all = np.concatenate(pooled_parts, axis=1)  # (N, B, d)

        if self.policy == "recall":
            assert self.predictor is not None, "recall policy needs a predictor"
            with spans.span("engine.predict"):
                pred_idx = spans.to_host(PE.predict_exit(
                    self.predictor, spans.to_device(pooled_all[-1]),
                    n_exits=len(self.exits)))
        elif self.policy == "branchynet":
            # confidence-style: run each sample layer-by-layer (batch=1) and
            # exit when consecutive exit embeddings agree (cos > tau).
            pred_idx = self._branchynet_exits(items)

        # 2+3) exit groups -> dense batched continuation from layer N
        tp = self.params["towers"][self.modality]
        plan = plan_exit_groups(pred_idx, self.exits, N)
        for exit_idx, exit_layer, ids in plan.batches(self.max_batch):
            hs = None  # the group's superficial states on the device
            with spans.span("engine.continue"):
                if exit_layer <= N:
                    # exit depth within the superficial prefix: embedding
                    # comes straight from the already-computed pooled state
                    # (free).
                    embs = spans.to_host(T.exit_embedding(
                        tp, spans.to_device(pooled_all[exit_layer - 1][ids]),
                        self.cfg.norm_eps))
                    layers_run = N  # superficial pass was still paid
                else:
                    fn = self._continue_fn(N, exit_layer)
                    hs = spans.to_device(h_sup[ids])
                    embs = spans.to_host(fn(self.params, self.lora, hs))
                    layers_run = exit_layer
            self.stats.group_batches += 1
            self.stats.layers_executed += float(len(ids) * layers_run)
            cached = None
            if self.cache_activations:
                # the store quantizes them on the device, one array an item
                cached = _unstack(spans.to_device(h_sup[ids]) if hs is None
                                  else hs)
            self.store.add_batch(
                uids[ids], embs, [exit_idx] * len(ids), [exit_layer] * len(ids),
                modality=self.modality, cached_hs=cached)
        # under an async bank-refresh policy, kick the scheduler now: the
        # freshly inserted rows scatter to the device while the host is
        # still between drains, instead of on the first query's critical
        # path (EdgeRAG-style index maintenance hidden behind serving)
        self.store.kick_bank_refresh()
        self.stats.n_embedded += len(uids)

    def _branchynet_exits(self, items: np.ndarray, tau: float = 0.95) -> np.ndarray:
        """Per-sample confidence exits (baseline; no batching by design)."""
        fn = jax.jit(lambda p, lo, x: IB.mem_embed_all_exits(
            p, self.cfg, self.recall, self.modality, x, lora=lo,
            **self.fw_kw)["exit_embs"])
        out = np.zeros(len(items), np.int64)
        for i in range(len(items)):
            embs = np.asarray(fn(self.params, self.lora,
                                 jnp.asarray(items[i:i + 1])))[:, 0]  # (n_exits, E)
            exit_i = len(self.exits) - 1
            for e in range(len(self.exits) - 1):
                if float(embs[e] @ embs[e + 1]) > tau:
                    exit_i = e
                    break
            out[i] = exit_i
        return out

    # -- refinement hook for the query runtime -----------------------------------

    def refine_fn(self) -> Callable:
        """Batched refinement hook for speculative retrieval round 3.

        Called with a uid array it returns ``{uid: fine_emb}`` for every uid
        with a cached activation, running ONE dense continuation per
        activation-shape group (chunked at ``max_batch``) instead of a B=1
        jit call per uid. Called with a scalar uid it returns the embedding
        or None (seed-compatible)."""
        start = self.recall.superficial_layers
        end = self.tower.n_layers

        def refine(uids):
            scalar = np.isscalar(uids) or isinstance(uids, (int, np.integer))
            uid_list = ([int(uids)] if scalar
                        else [int(u) for u in np.asarray(uids).ravel()])
            cached = self.store.cached_activations(uid_list)
            # cached tensors are superficial hidden states: resume from layer
            # N. Group by shape (one group per modality/sequence length).
            groups: Dict[Tuple[int, ...], List[int]] = {}
            for u in uid_list:
                if u in cached:
                    groups.setdefault(tuple(cached[u][0].shape), []).append(u)
            out: Dict[int, np.ndarray] = {}
            fn = self._continue_fn(start, end)
            for us in groups.values():
                for i in range(0, len(us), self.max_batch):
                    chunk = us[i:i + self.max_batch]
                    with spans.span("engine.refine_stack"):
                        h = np.stack([cached[u][0] for u in chunk])
                    with spans.span("engine.refine_continue"):
                        embs = spans.to_host(fn(self.params, self.lora,
                                                spans.to_device(h)))
                    out.update(zip(chunk, embs))
            if scalar:
                return out.get(int(uids))
            return out
        return refine
