"""The serving path's standard program: HMult + relin, rescale, HRot by 1
(fused AutoU∘KS), HAdd — the HMult/HRot primitive pair, on fresh
ciphertexts at the configuration's input level with every slot filled.

    prod = rescale(x · y);  out = rot(prod, 1) + prod
"""
from __future__ import annotations

import numpy as np

from fhebench.reference import Instance, Operand, ScaleTracker


def rotations(cfg: dict) -> tuple:
    return (1,)


def build(cfg: dict, q, rng: np.random.Generator) -> Instance:
    slots = cfg["N"] // 2
    limbs = cfg["input_limbs"]
    t = ScaleTracker(q, cfg["rescale_primes"])
    # inputs at the product of the primes the program's one rescale drops,
    # so the output returns to the input scale
    scale = t.top_primes(limbs)
    x = rng.uniform(-1.0, 1.0, slots)
    y = rng.uniform(-1.0, 1.0, slots)
    t.input("x", limbs, scale)
    t.input("y", limbs, scale)
    t.mult("hmult", "prod", "x", "y")
    t.unary("hrot", "rot", "prod", 1)
    t.add("out", "rot", "prod")
    return Instance(ops=t.ops,
                    inputs={"x": Operand(x, scale, limbs),
                            "y": Operand(y, scale, limbs)},
                    plaintexts=t.plaintexts, outputs=("out",))
