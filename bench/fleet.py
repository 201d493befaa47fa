"""The one traffic generator: a fleet built from a configuration file, a
traffic mix and a seed.

A configuration (``configs/<name>.json``) fixes the deployment: fleet
size, image and piece size, links, choke settings, kernel backend and
network layout (islands, their latencies and the ALTO cost map the
tracker serves).  A traffic mix (``traffic/<name>.json``) fixes how the
volunteers arrive:

- ``initial``: ``"fetch"``, every volunteer joins at virtual t=0 and
  fetches the image (a flash crowd);
- ``warmup_virtual_s``: the virtual span simulated during set-up;
- ``agent``: `AgentConfig` fields the mix sets on top of the
  configuration's.

The seed changes the order of the work, never its amount: the volunteer
speeds are a fixed set, dealt out in a seeded order.
"""
from __future__ import annotations

import random
from typing import Dict, List, Optional

import numpy as np

from repro.core import (Agent, AgentConfig, SimRuntime, TrackerConfig,
                        TrackerServer)
from repro.core.runtime import LinkModel
from repro.core.swarm_arrays import SwarmHub
from repro.core.topology import Topology
from repro.core.workunit import make_prime_app

APP_ID = "app"
HOST = "host"
# ALTO cost map (RFC 7285 "routingcost", numerical): 0 inside an island,
# one step per started 10 ms of one-way latency across islands, at most
# COST_CAP
COST_STEP_S = 0.01
COST_CAP = 15


def alto_costs(latency_s: List[List[float]]) -> List[List[int]]:
    """The cost map the tracker serves, from the inter-island latencies."""
    k = len(latency_s)
    return [[0 if a == b else
             max(1, min(COST_CAP, 1 + int(latency_s[a][b] / COST_STEP_S)))
             for b in range(k)] for a in range(k)]


class Fleet:
    """One simulated deployment: runtime, tracker, origin, volunteers and
    the batched hub."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        if traffic["initial"] != "fetch":
            raise ValueError(f"unknown initial {traffic['initial']!r}")
        self.config = config
        self.traffic = traffic
        self.rng = random.Random(seed)
        n = int(config["n_volunteers"])
        self.n_volunteers = n
        link_Bps = float(config["uplink_mbps"]) * 1e6 / 8
        down_Bps = float(config["downlink_mbps"]) * 1e6 / 8
        self.names = [f"V{i:04d}" for i in range(n)]
        # speeds 1.0 down to 1 - spread, dealt out in a seeded order
        spread = float(config["speed_spread"])
        speeds = [1.0 - spread * i / n for i in range(n)]
        self.rng.shuffle(speeds)
        self.speed = dict(zip(self.names, speeds))

        self.topology = self._topology()
        self.rt = SimRuntime(link=LinkModel(uplink_Bps=link_Bps,
                                            downlink_Bps=down_Bps),
                             topology=self.topology)
        self.rt.add_node(TrackerServer(
            config=TrackerConfig(**config["tracker"]),
            topology=self.topology))
        self.hub = SwarmHub(backend=config["backend"])
        self.rt.crash_hooks.append(self.hub.node_gone)
        if self.topology is not None:
            self.hub.set_topology(self.topology)
        self.agent_cfg = dict(config["agent"], **traffic.get("agent", {}))

        self.piece_bytes = int(config["piece_bytes"])
        image_bytes = int(config["image_bytes"])
        self.n_pieces = -(-image_bytes // self.piece_bytes)
        self.host = Agent(HOST, config=AgentConfig(**self.agent_cfg),
                          hub=self.hub)
        self.rt.add_node(self.host)
        work = config["work"]
        app = make_prime_app(
            APP_ID, HOST, int(work["lo"]), int(work["hi"]),
            n_parts=int(work["parts_per_volunteer"]) * n,
            sim_time_per_number=float(work["sim_time_per_number"]),
            m_min=int(work["m_min"]), swarm=True, app_bytes=image_bytes,
            piece_bytes=self.piece_bytes)
        self.host.host_app(app)
        self.manifest = app.manifest
        if self.manifest.n_pieces != self.n_pieces:
            raise ValueError("the image does not split into the stated "
                             "pieces")

        self.agents: List[Agent] = []
        for name in self.names:
            a = Agent(name, config=AgentConfig(**self.agent_cfg),
                      hub=self.hub)
            self.rt.add_node(a, speed=self.speed[name])
            self.agents.append(a)

    # ------------------------------------------------------------------ #
    def _topology(self) -> Optional[Topology]:
        spec = self.config.get("topology")
        if not spec:
            return None
        lat = spec["latency_s"]
        k = len(lat)
        self.cost = alto_costs(lat)
        # the volunteers are dealt out over the islands by name, an equal
        # share each, whatever the seed: who shares an island decides whom
        # the cost order prefers, so a seeded deal would change the work
        islands: Dict[str, int] = {HOST: 0}
        for j, name in enumerate(self.names):
            islands[name] = (j + 1) % k
        self.islands = islands
        return Topology(islands, k, lat, cost=self.cost)

    def live(self) -> List[Agent]:
        """The origin and every volunteer still running, by name."""
        nodes = self.rt.nodes
        return sorted((a for a in [self.host] + self.agents
                       if a.node_id in nodes), key=lambda a: a.node_id)

    def rules(self):
        """The reference's decision rules, from this deployment's files."""
        from bench.reference import Rules
        a = self.agent_cfg
        cost = None if self.topology is None else \
            np.asarray(self.cost, dtype=np.int64)
        return Rules(app_id=APP_ID, n_pieces=self.n_pieces,
                     piece_bytes=self.piece_bytes,
                     pipeline=int(a["piece_pipeline"]),
                     slots=int(a["upload_slots"]),
                     endgame_dup=int(a["endgame_dup"]),
                     optimistic_every=int(a["optimistic_every"]),
                     rate_window_s=float(a["rate_window_s"]), cost=cost,
                     island=dict(getattr(self, "islands", {})))

    def verified_pieces(self) -> int:
        """Pieces verified so far by every volunteer."""
        total = 0
        for a in self.agents:
            inv = a.px.inventories.get(APP_ID)
            if inv is not None:
                total += len(inv.have)
        return total

    def outcomes(self) -> Dict[str, int]:
        """What the volunteers' own records say of their requests:
        ``delivered`` (pieces verified, plus copies that lost an endgame
        race), ``corrupt`` (holders caught sending a piece that failed
        verification) and ``overdue`` (requests unanswered past the
        volunteer's stall limit, now)."""
        now = self.rt.now()
        delivered = corrupt = overdue = 0
        for a in self.agents:
            px = a.px
            inv = px.inventories.get(APP_ID)
            delivered += (len(inv.have) if inv is not None else 0) \
                + px.dup_piece_data
            corrupt += len(px.bad_peers.get(APP_ID, ()))
            stall = a.cfg.piece_timeout_s or a.cfg.work_timeout_s
            for asked in px.pending.get(APP_ID, {}).values():
                overdue += sum(1 for t in asked.values() if now - t > stall)
        return {"delivered": delivered, "corrupt": corrupt,
                "overdue": overdue}
