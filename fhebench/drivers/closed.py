"""Closed-loop traffic: a fixed set of clients, each sending its next request
as soon as its previous answer is back on the host's side of the device.

The mix file gives the client count: ``"clients": "max_batch"`` (the
deployment's batch, spread evenly over its tenants) or
``"clients_per_tenant": n``. Requests of one program sent together stay in
lockstep through the engine, so every wave has the same batch shapes.

A request's latency runs from just before ``submit`` to the moment its
outputs are ready on the device (``block_until_ready``). The window opens at
the first submit and closes at the first completion at or after
``seconds``; requests still in flight then are drained afterwards, checked,
and left out of the window's counts. Every ``submit``, ``step``, ``block``
and ``harvest`` inside the window is timed on the same clock
(``Loop.phases``), so a stall in the window can be placed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time


def client_tenants(traffic: dict, cfg: dict, tenants: list) -> list:
    """The tenant of each client."""
    if "clients_per_tenant" in traffic:
        per = int(traffic["clients_per_tenant"])
    elif traffic.get("clients") == "max_batch":
        per, rem = divmod(cfg["max_batch"], len(tenants))
        if rem:
            raise ValueError(f"max_batch {cfg['max_batch']} does not split "
                             f"over {len(tenants)} tenants")
    else:
        raise ValueError(f"closed-loop mix needs clients or "
                         f"clients_per_tenant: {traffic}")
    return [t for t in tenants for _ in range(per)]


@dataclasses.dataclass
class Record:
    client: int
    rid: int
    submitted: float
    done: float = float("nan")
    status: str = "active"
    in_window: bool = False


@dataclasses.dataclass
class Loop:
    engine: object
    new_request: object          # client index → FheRequest
    n_clients: int
    harvest: object              # finished "ok" FheRequest → None
    annotate: object             # phase name → context manager
    clock: object = time.perf_counter
    records: list = dataclasses.field(default_factory=list)
    live: dict = dataclasses.field(default_factory=dict)   # rid → Record
    t0: float = float("nan")
    t_close: float = float("nan")
    open: bool = False
    steps: int = 0
    phases: list = dataclasses.field(default_factory=list)
    # (phase, seconds from the window's start, seconds) inside the window

    @contextlib.contextmanager
    def _phase(self, name: str):
        t = self.clock()
        with self.annotate(name):
            yield
        if self.open:
            self.phases.append((name, t - self.t0, self.clock() - t))

    def _submit(self, client: int) -> None:
        req = self.new_request(client)
        rec = Record(client, req.rid, self.clock())
        with self._phase("submit"):
            self.engine.submit(req)
        if req.done:                     # rejected at admission
            rec.status, rec.done = req.status, self.clock()
            self.records.append(rec)
            return
        self.live[req.rid] = rec

    def _retire(self) -> list:
        """Block on the step's finished requests; returns their clients."""
        import jax
        eng = self.engine
        done, failed = list(eng.completed), list(eng.failed)
        eng.completed.clear()
        eng.failed.clear()
        if done:
            with self._phase("block"):
                jax.block_until_ready([(c.a.data, c.b.data) for r in done
                                       for c in r.result().values()])
        now = self.clock()
        clients = []
        for req in done + failed:
            rec = self.live.pop(req.rid)
            rec.done, rec.status = now, req.status
            rec.in_window = self.open
            if req.status == "ok":
                with self._phase("harvest"):
                    self.harvest(req)
            req.env.clear()
            self.records.append(rec)
            clients.append(rec.client)
        return clients

    def run_window(self, seconds: float, give_up_s: float) -> None:
        """Serve until the first completion at or after ``seconds``."""
        self.open = True
        self.t0 = self.clock()
        for c in range(self.n_clients):
            self._submit(c)
        while True:
            with self._phase("step"):
                self.engine.step()
            self.steps += 1
            finished = self._retire()
            now = self.clock()
            if finished and now - self.t0 >= seconds:
                self.t_close = now
                self.open = False
                return
            if now - self.t0 > give_up_s:
                raise TimeoutError(f"no completion within {give_up_s} s")
            for c in finished:
                self._submit(c)

    def drain(self, give_up_s: float) -> None:
        """Finish the requests still in flight (not counted in the window)."""
        t_stop = self.clock() + give_up_s
        while self.live:
            if self.clock() > t_stop:
                for rec in self.live.values():
                    rec.status = "never_finished"
                    self.records.append(rec)
                self.live.clear()
                return
            with self._phase("step"):
                self.engine.step()
            self._retire()

    def window_records(self) -> list:
        return [r for r in self.records if r.in_window]
