"""Continuous-batching serving engine with (d, p, w)-aware admission.

Requests are the serving analogue of the paper's applications: each carries
  d — prompt+generation bytes,
  w — measured decode seconds (running average per bucket),
  p — how many requests of this bucket were served.
The engine publishes these units (like the tracker's list) and admission
prefers short-w buckets when the queue saturates — the volunteer's
"judge by d and w" heuristic as a scheduler policy.

Execution: fixed-shape prefill (padded to bucket) + one jitted decode step
for the whole active batch; finished slots are refilled from the queue
(continuous batching).  The KV cache is one fixed-size pool tensor per
layer — slots are rows, so refill is a dynamic row update, the TPU-friendly
variant of paged attention at slot granularity.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.parallel.sharding import init_params, sharding_ctx, infer_rules
from repro.training.train_state import make_decode_step


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray                 # (S,) int32
    max_new: int = 16
    arrived: float = 0.0
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    started: float = 0.0
    finished: float = 0.0


@dataclass
class ServeConfig:
    slots: int = 4                     # concurrent sequences
    max_len: int = 256                 # cache length
    prefill_bucket: int = 64


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, sc: ServeConfig, mesh=None):
        self.cfg = cfg
        self.sc = sc
        self.mesh = mesh
        self.params = params
        # checkpoint `extra` dict when this engine cold-started from the
        # swarm (from_swarm); None for directly-constructed engines
        self.restore_extra: Optional[dict] = None
        self.rules = infer_rules(cfg)
        self.queue: collections.deque = collections.deque()
        self.active: Dict[int, Request] = {}
        self.slot_req: List[Optional[int]] = [None] * sc.slots
        self.metrics = {"p": collections.Counter(),
                        "w": collections.defaultdict(float),
                        "d": collections.defaultdict(float)}
        self._init_cache()
        self._decode = jax.jit(make_decode_step(cfg, mesh))
        self._next_id = 0

    # ------------------------------------------------------------------ #
    @classmethod
    def from_swarm(cls, cfg: ModelConfig, template, sc: ServeConfig, *,
                   agent, app_id: str, mesh=None, pod_axis: str = "pod",
                   workdir=None) -> "ServingEngine":
        """Cold-start a replica from the distribution swarm.

        The replica's `agent` leeched the checkpoint Application like any
        other volunteer; the moment its piece set completes
        (`app_id in agent.images`) this reassembles the step image,
        re-hashes its content against the manifest, restores the params
        into `template`'s structure, and — when a mesh with a pod axis is
        given — fans the freshly-landed bytes out intra-pod over the
        `weight_torrent` ppermute ring, so only one host per pod pulls
        from the swarm.  Raises if the piece set is still incomplete.
        """
        from repro.checkpoint.swarm_restore import restore_from_agent
        params, extra = restore_from_agent(agent, app_id, template,
                                           workdir=workdir)
        if mesh is not None and pod_axis in getattr(mesh, "shape", {}):
            from repro.parallel.weight_torrent import torrent_broadcast
            params = torrent_broadcast(params, mesh, axis=pod_axis)
        else:
            # onto the device once, not re-sent with every decode step
            params = jax.device_put(params)
        eng = cls(cfg, params, sc, mesh=mesh)
        eng.restore_extra = extra
        return eng

    def _init_cache(self):
        tree = M.cache_specs_tree(self.cfg, self.sc.slots, self.sc.max_len)
        self.caches = init_params(jax.random.PRNGKey(0), tree)
        self.caches["index"] = jnp.zeros((), jnp.int32)
        self.positions = np.zeros(self.sc.slots, np.int64)
        self.tokens = np.zeros((self.sc.slots, 1), np.int32)

    def submit(self, prompt: np.ndarray, max_new: int = 16) -> int:
        rid = self._next_id
        self._next_id += 1
        req = Request(rid, np.asarray(prompt, np.int32), max_new,
                      arrived=time.monotonic())
        self.queue.append(req)
        return rid

    def _bucket(self, req: Request) -> int:
        b = self.sc.prefill_bucket
        return ((len(req.prompt) + b - 1) // b) * b

    def _admit(self) -> None:
        """Fill free slots; prefer short-w buckets under saturation."""
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        if not free or not self.queue:
            return
        pending = sorted(
            self.queue,
            key=lambda r: self.metrics["w"].get(self._bucket(r), 0.0))
        for slot in free:
            if not pending:
                break
            req = pending.pop(0)
            self.queue.remove(req)
            self._prefill_into_slot(slot, req)

    def _prefill_into_slot(self, slot: int, req: Request) -> None:
        """Sequential prefill through the decode step (slot-local)."""
        req.started = time.monotonic()
        self.active[req.req_id] = req
        self.slot_req[slot] = req.req_id
        # reset this slot's position; feed prompt tokens one step at a time
        # through the shared decode path (slot-granular continuous batching;
        # a bucketed prefill graph is the natural next optimisation).
        self.positions[slot] = 0
        toks = req.prompt
        for t in toks[:-1]:
            self.tokens[slot, 0] = int(t)
            self._step_decode(only_slot=slot)
        self.tokens[slot, 0] = int(toks[-1])

    def _step_decode(self, only_slot: Optional[int] = None) -> np.ndarray:
        batch = {"tokens": jnp.asarray(self.tokens)}
        if self.cfg.mrope:
            pos = jnp.asarray(
                np.broadcast_to(self.positions[None, :, None],
                                (3, self.sc.slots, 1)).astype(np.int32))
            batch["positions"] = pos
        # per-slot positions: each sequence writes/masks at its own index
        self.caches["index"] = jnp.asarray(self.positions.astype(np.int32))
        next_tok, self.caches = self._decode(self.params, batch, self.caches)
        if only_slot is not None:
            # prefill microstep: only the target slot advances; other slots
            # rewrite their current position with identical K/V (idempotent)
            self.positions[only_slot] += 1
        else:
            self.positions += 1
        return np.asarray(next_tok)

    def step(self) -> int:
        """One engine tick: admit, decode the full batch, retire finished."""
        self._admit()
        if not self.active:
            return 0
        t0 = time.monotonic()
        nxt = self._step_decode()
        dt = time.monotonic() - t0
        produced = 0
        for slot, rid in enumerate(self.slot_req):
            if rid is None:
                continue
            req = self.active[rid]
            tok = int(nxt[slot])
            req.out_tokens.append(tok)
            self.tokens[slot, 0] = tok
            produced += 1
            if len(req.out_tokens) >= req.max_new:
                req.done = True
                req.finished = time.monotonic()
                b = self._bucket(req)
                self.metrics["p"][b] += 1
                self.metrics["w"][b] = (
                    0.8 * self.metrics["w"].get(b, dt) + 0.2 *
                    (req.finished - req.started))
                self.metrics["d"][b] += 4.0 * (len(req.prompt)
                                               + len(req.out_tokens))
                self.slot_req[slot] = None
                del self.active[rid]
        return produced

    def run_until_drained(self, max_ticks: int = 10_000) -> List[Request]:
        done: List[Request] = []
        seen = set()
        for _ in range(max_ticks):
            if not self.queue and not self.active:
                break
            self.step()
        return done

    def published_units(self) -> dict:
        """The tracker-style (d, p, w) listing per prompt bucket."""
        return {b: {"d": self.metrics["d"][b], "p": self.metrics["p"][b],
                    "w": self.metrics["w"][b]}
                for b in self.metrics["p"]}
