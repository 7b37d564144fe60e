"""Operations and bytes of min-max dequantization (Eq. 2) of ``elems``
codes: one multiply and one add per value; each code read once at its
width and each value written once at ``out_bytes``."""
from __future__ import annotations


def cost(elems, bits=8, out_bytes=4):
    code_bytes = 1 if bits <= 8 else 2
    return {"flops": 2 * elems, "bytes": elems * (code_bytes + out_bytes)}
