"""Reads the served answers back and compares them with the reference.

While the window runs, :class:`Harvest` keeps the first ``limbs`` RNS limbs
of each output ciphertext (limbs are independent residues, so they are the
ciphertext modulo q₀·…·q_{limbs−1}, which is wide enough for the scaled
message) and lets the engine free the rest. After the window, the kept
limbs are decrypted with the tenant's secret key, lifted to integers by the
CRT (Garner, exact in int64), and decoded by the canonical embedding
``z_j = m(ζ^{5^j}) / Δ`` computed here with an FFT. The number compared is
the widest gap ``|decoded − reference|`` over every slot of every output of
every checked request.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from fhebench import reference


@dataclasses.dataclass
class Answer:
    tenant: str
    entry: int                   # index of the request's pool entry
    register: str
    scale: float
    basis: tuple                 # the kept primes
    a: object                    # (limbs, N) device arrays, NTT domain
    b: object


class Harvest:
    """Keeps the answer limbs of finished requests (``harvest(req)``)."""

    def __init__(self, limbs: int, entry_of: dict):
        self.limbs = limbs
        self.entry_of = entry_of         # rid → pool entry index
        self.answers: list[Answer] = []

    def __call__(self, req) -> None:
        for reg, ct in req.result().items():
            n = self.limbs
            if ct.a.domain != "ntt" or ct.b.domain != "ntt":
                raise ValueError(f"answer {reg} of request {req.rid} is not "
                                 "in the NTT domain")
            self.answers.append(Answer(
                req.tenant, self.entry_of[req.rid], reg,
                float(ct.scale), tuple(ct.basis[:n]), ct.a.data[:n],
                ct.b.data[:n]))


def crt_centered(res: np.ndarray, basis) -> np.ndarray:
    """(…, 3, N) residues mod three primes → centered integers as float64.

    Garner's mixed radix v0 + v1·q0 + v2·q0·q1 with the top digit centered;
    exact for messages far below (q0·q1·q2)/2."""
    if len(basis) != 3:
        raise ValueError(f"CRT over 3 primes, got {len(basis)}")
    q0, q1, q2 = (int(q) for q in basis)
    r = res.astype(np.int64)
    v0 = r[..., 0, :]
    v1 = ((r[..., 1, :] - v0 % q1) % q1) * pow(q0, -1, q1) % q1
    t = (r[..., 2, :] - v0 % q2 - (v1 * (q0 % q2)) % q2) % q2
    v2 = (t * pow(q0 * q1 % q2, -1, q2)) % q2
    v2 = np.where(v2 > q2 // 2, v2 - q2, v2)
    # v0 + v1·q0 < 2⁶² is exact; the top digit is small for a small message
    return (v0 + v1 * q0).astype(np.float64) + v2.astype(np.float64) * (
        float(q0) * float(q1))


def decode(coeffs: np.ndarray, scale: float) -> np.ndarray:
    """(…, N) integer coefficients → (…, N/2) complex slots."""
    N = coeffs.shape[-1]
    M = 2 * N
    ev = np.fft.ifft(coeffs, n=M, axis=-1) * M       # m(e^{2πi t / M}), all t
    rot = np.empty(N // 2, dtype=np.int64)
    v = 1
    for j in range(N // 2):
        rot[j] = v
        v = v * 5 % M
    return ev[..., rot] / scale


def decrypt_limbs(answers: list[Answer], sks: dict, N: int,
                  chunk: int = 32) -> np.ndarray:
    """Plain coefficient residues (M, limbs, N) of the kept answer limbs."""
    import jax.numpy as jnp

    from repro.core import keys as K
    from repro.core import poly as pl
    out = np.empty((len(answers), len(answers[0].basis), N), np.uint32) \
        if answers else np.empty((0, 3, N), np.uint32)
    groups: dict = {}
    for i, ans in enumerate(answers):
        groups.setdefault((ans.tenant, ans.basis), []).append(i)
    for (tenant, basis), idx in groups.items():
        for s in range(0, len(idx), chunk):
            part = idx[s:s + chunk]
            pad = part + [part[0]] * (chunk - len(part))   # one shape
            a = jnp.stack([answers[i].a for i in pad])
            b = jnp.stack([answers[i].b for i in pad])
            ct = K.Ciphertext(pl.RnsPoly(a, basis, pl.NTT),
                              pl.RnsPoly(b, basis, pl.NTT), 1.0)
            m = K.decrypt(ct, sks[tenant])
            out[part] = m[:len(part)]
    return out


def widest_gap(answers: list[Answer], residues: np.ndarray, refs: dict
               ) -> float:
    """Largest |decoded − reference| over all slots of all answers;
    ``refs[(entry, register)]`` is the reference slot vector."""
    gap = 0.0
    for ans, res in zip(answers, residues):
        got = decode(crt_centered(res, ans.basis), ans.scale)
        want = refs[(ans.entry, ans.register)]
        gap = max(gap, float(np.max(np.abs(got - want))))
    return gap


def reference_outputs(instances, dtype=np.float64) -> dict:
    """(pool entry, register) → slot vector of the plain program."""
    out = {}
    for e, inst in enumerate(instances):
        for reg, vals in reference.evaluate(inst, dtype).items():
            out[(e, reg)] = vals
    return out


def control_answers(answers: list[Answer], instances, sks: dict, N: int,
                    seed: int) -> list[Answer]:
    """The control put in the program's place: each answer replaced by an
    encryption, under its tenant's key, at its own scale and kept primes,
    of its request's reference evaluated in float32. The check then reads
    these as it reads the program's answers."""
    from repro.core import encoding as enc
    from repro.core import keys as K
    low = reference_outputs(instances, np.float32)
    rng = np.random.default_rng(seed)
    made: dict = {}
    out = []
    for ans in answers:
        key = (ans.tenant, ans.entry, ans.register, ans.scale, ans.basis)
        if key not in made:
            vals = low[(ans.entry, ans.register)].astype(np.float64)
            res = enc.encode(vals, ans.scale, ans.basis, N)
            made[key] = K.encrypt(res, ans.scale, sks[ans.tenant],
                                  ans.basis, N, rng=rng)
        ct = made[key]
        out.append(dataclasses.replace(ans, a=ct.a.data, b=ct.b.data))
    return out
