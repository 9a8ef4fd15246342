"""A deployment built from the seed: parameter set, tenants with their keys
on the device, and a pool of client inputs (encrypted under each tenant's
key) that the traffic draws requests from.

The key material, the encryption and the engine are the program's own; the
slot values and their levels and scales come from the benchmark's programs
(``programs/<name>.py``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import time

import numpy as np


def seed_int(*parts: int) -> int:
    """A 64-bit integer drawn from ``parts`` (any size of int)."""
    ss = np.random.SeedSequence([int(p) for p in parts])
    return int(ss.generate_state(1, np.uint64)[0])


def params_of(cfg: dict):
    from repro.core import params as prm
    return prm.make_params(N=cfg["N"], L=cfg["L"], K=cfg["K"],
                           dnum=cfg["dnum"],
                           rescale_primes=cfg["rescale_primes"])


@dataclasses.dataclass
class PoolEntry:
    tenant: str
    inst: object                 # reference.Instance: the plain request
    inputs: dict                 # register → Ciphertext under the tenant's key
    plaintexts: dict             # pmult key → (RnsPoly in NTT domain, scale)


@dataclasses.dataclass
class Deployment:
    params: object
    store: object                # repro.serve.TenantKeyStore
    tenants: list
    pool: list                   # [PoolEntry], ``pool_per_tenant`` per tenant
    program_ops: tuple           # HeOps every request runs
    timings: dict                # set-up phase → seconds

    def request(self, entry: PoolEntry):
        from repro.serve import FheRequest
        return FheRequest(tenant=entry.tenant, program=self.program_ops,
                          inputs=dict(entry.inputs),
                          outputs=entry.inst.outputs,
                          plaintexts=entry.plaintexts)


def entry_rng(seed: int, tenant: int, j: int) -> np.random.Generator:
    """The generator of pool entry ``j`` of tenant ``tenant``."""
    return np.random.default_rng(seed_int(seed, 2, tenant, j))


def plain_pool(cfg: dict, program, seed: int) -> list:
    """The plain requests (``reference.Instance``) of the pool, in pool
    order, without keys or encryption."""
    q = params_of(cfg).q
    return [program.build(cfg, q, entry_rng(seed, i, j))
            for i in range(cfg["tenants"])
            for j in range(cfg["pool_per_tenant"])]


def build(cfg: dict, program, seed: int) -> Deployment:
    """Keys for every tenant and the input pool, all drawn from ``seed``."""
    import jax
    import jax.numpy as jnp

    from repro.core import encoding as enc
    from repro.core import keys as K
    from repro.core import poly as pl
    from repro.serve import HeOp, TenantKeyStore

    timings = {}
    p = params_of(cfg)
    n_t = cfg["tenants"]
    tenants = [f"tenant{i}" for i in range(n_t)]
    t0 = time.perf_counter()
    store = TenantKeyStore(max_resident=max(2, n_t))
    rots = program.rotations(cfg)
    for i, name in enumerate(tenants):
        store.register(name, K.keygen(p, rotations=rots,
                                      seed=seed_int(seed, 1, i)))
    timings["keygen_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    encoded = {}                 # (values digest, scale, limbs) → RnsPoly

    def plaintext(op):
        key = (hashlib.sha1(op.values.tobytes()).digest(), op.scale,
               op.limbs)
        if key not in encoded:
            basis = p.q[:op.limbs]
            res = enc.encode(op.values, op.scale, basis, p.N)
            encoded[key] = pl.RnsPoly(jnp.asarray(res), basis,
                                      pl.COEFF).to_ntt()
        return encoded[key], op.scale

    pool, ops = [], None
    for i, name in enumerate(tenants):
        sk = store.keyset(name).sk
        for j in range(cfg["pool_per_tenant"]):
            rng = entry_rng(seed, i, j)
            inst = program.build(cfg, p.q, rng)
            if ops is None:
                ops = tuple(HeOp(k, d, tuple(s), a) for k, d, s, a in inst.ops)
            cts = {}
            for reg, op in inst.inputs.items():
                basis = p.q[:op.limbs]
                res = enc.encode(op.values, op.scale, basis, p.N)
                cts[reg] = K.encrypt(res, op.scale, sk, basis, p.N, rng=rng)
            pts = {k: plaintext(op) for k, op in inst.plaintexts.items()}
            pool.append(PoolEntry(name, inst, cts, pts))
    jax.block_until_ready([(c.a.data, c.b.data) for e in pool
                           for c in e.inputs.values()])
    timings["encrypt_s"] = time.perf_counter() - t0
    return Deployment(params=p, store=store, tenants=tenants,
                      pool=pool, program_ops=ops, timings=timings)
