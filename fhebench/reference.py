"""The plain reference: an HE program evaluated on its slot values.

A program (``programs/<name>.py``) describes one request as plain data — a
straight-line list of ``(kind, dst, srcs, arg)`` ops over named registers,
the slot values, scale and level of each input ciphertext and plaintext —
and :func:`evaluate` runs those ops on the slot vectors with numpy. It
imports nothing of the program under test and takes none of its tables.

Semantics of each op kind on slot vectors (CKKS, real slots):
``hadd`` a+b, ``hsub`` a−b, ``hmult`` a·b, ``square`` a², ``pmult`` a·p,
``rescale`` a (only the scale bookkeeping changes), ``hrot r`` a rotated
left by r (slot j takes slot j+r, cyclically), ``add_const c`` a+c,
``mul_const c`` a·c, ``conjugate`` conj(a).

``dtype=np.float64`` is the reference; ``np.float32`` is the control of a
lower precision put in the program's place.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Operand:
    """Slot values of one input, with the scale and level it is encoded at."""
    values: np.ndarray           # (slots,) float64
    scale: float
    limbs: int


@dataclasses.dataclass
class Instance:
    """One request of a program, as plain data."""
    ops: list                    # [(kind, dst, srcs, arg), ...]
    inputs: dict                 # register → Operand (encrypted by the client)
    plaintexts: dict             # pmult key → Operand (encoded, not encrypted)
    outputs: tuple               # registers the request returns


def evaluate(inst: Instance, dtype=np.float64) -> dict:
    """Output register → slot vector, computed in ``dtype``."""
    regs = {k: np.asarray(v.values, dtype=dtype) for k, v in inst.inputs.items()}
    pts = {k: np.asarray(v.values, dtype=dtype)
           for k, v in inst.plaintexts.items()}
    for kind, dst, srcs, arg in inst.ops:
        a = regs[srcs[0]]
        if kind == "hadd":
            out = a + regs[srcs[1]]
        elif kind == "hsub":
            out = a - regs[srcs[1]]
        elif kind == "hmult":
            out = a * regs[srcs[1]]
        elif kind == "square":
            out = a * a
        elif kind == "pmult":
            out = a * pts[arg]
        elif kind == "rescale":
            out = a
        elif kind == "hrot":
            out = np.roll(a, -int(arg))
        elif kind == "add_const":
            out = a + dtype(arg)
        elif kind == "mul_const":
            out = a * dtype(arg)
        elif kind == "conjugate":
            out = np.conj(a)
        else:
            raise ValueError(f"no reference for op kind {kind!r}")
        regs[dst] = out.astype(dtype, copy=False)
    return {name: regs[name] for name in inst.outputs}


class ScaleTracker:
    """Level and scale of each register along a straight-line program, with
    the arithmetic the CKKS layer applies (a rescale divides by the top
    ``rescale_primes`` primes of the level, one at a time).

    Programs use it to encode each plaintext at the scale that lands the
    product where the program needs it, so the ciphertexts that an ``hadd``
    joins carry the same scale.
    """

    def __init__(self, q, rescale_primes: int):
        self.q = tuple(int(x) for x in q)
        self.rp = int(rescale_primes)
        self.ops: list = []
        self.state: dict = {}            # register → (limbs, scale)
        self.plaintexts: dict = {}       # key → Operand

    def input(self, reg: str, limbs: int, scale: float) -> None:
        self.state[reg] = (limbs, float(scale))

    def top_primes(self, limbs: int) -> float:
        """Product of the primes a rescale at ``limbs`` limbs drops."""
        p = 1.0
        for i in range(self.rp):
            p *= self.q[limbs - 1 - i]
        return p

    def _rescaled(self, limbs: int, scale: float):
        for i in range(self.rp):
            scale = scale / self.q[limbs - 1 - i]
        return limbs - self.rp, scale

    def rescale(self, dst: str, src: str) -> None:
        self.ops.append(("rescale", dst, (src,), None))
        self.state[dst] = self._rescaled(*self.state[src])

    def pmult(self, dst: str, src: str, key: str, values,
              target_scale: float | None = None,
              rescale: bool = True) -> None:
        """``dst = src ⊙ plaintext``, rescaled unless ``rescale=False``. The
        plaintext is encoded so that ``dst`` ends at ``target_scale``
        (default: ``src``'s scale) once it has been rescaled."""
        limbs, s = self.state[src]
        target = s if target_scale is None else float(target_scale)
        pt_scale = target * self.top_primes(limbs) / s
        self.plaintexts[key] = Operand(np.asarray(values, np.float64),
                                       pt_scale, limbs)
        self.ops.append(("pmult", dst, (src,), key))
        self.state[dst] = (limbs, s * pt_scale)
        if rescale:
            self.rescale(dst, dst)

    def scale(self, reg: str) -> float:
        return self.state[reg][1]

    def mult(self, kind: str, dst: str, a: str, b: str | None = None) -> None:
        """``dst = rescale(a·b)`` (``square`` when ``b`` is None)."""
        la, sa = self.state[a]
        if b is None:
            self.ops.append(("square", dst, (a,), None))
            self.state[dst] = (la, sa * sa)
        else:
            lb, sb = self.state[b]
            if la != lb:
                raise ValueError(f"{kind} of {a}@{la} and {b}@{lb} limbs")
            self.ops.append((kind, dst, (a, b), None))
            self.state[dst] = (la, sa * sb)
        self.rescale(dst, dst)

    def add(self, dst: str, a: str, b: str) -> None:
        (la, sa), (lb, sb) = self.state[a], self.state[b]
        if la != lb or abs(sa - sb) > 1e-12 * sa:
            raise ValueError(f"hadd of {a}@({la}, {sa}) and {b}@({lb}, {sb})")
        self.ops.append(("hadd", dst, (a, b), None))
        self.state[dst] = (la, sa)

    def unary(self, kind: str, dst: str, src: str, arg) -> None:
        """Ops that keep level and scale: ``hrot``, ``add_const``."""
        self.ops.append((kind, dst, (src,), arg))
        self.state[dst] = self.state[src]
