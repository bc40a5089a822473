"""Operations and bytes of a CNN graph, counted from its shapes.

The count is the direct convolution's, whatever algorithm the program
picks for a layer: a layer run as Winograd or kn2row is charged the same
work as im2col, so swapping algorithms never makes the count stale.
Winograd F(4,3) does fewer multiplies than it is charged for here.

Least bytes are each layer's input, weights (with bias) and output, read
or written once, at the byte width of the arrays the run feeds.
"""
from __future__ import annotations

from typing import Dict, List


def conv_flops(m, batch: int) -> int:
    """2 * K1 * K2 * Cin * Cout * O1 * O2 * B."""
    return 2 * m.k1 * m.k2 * m.c_in * m.c_out * m.o1 * m.o2 * batch


def conv_bytes(m, batch: int, itemsize: int) -> int:
    elems = (batch * m.h1 * m.h2 * m.c_in
             + m.k1 * m.k2 * m.c_in * m.c_out + m.c_out
             + batch * m.o1 * m.o2 * m.c_out)
    return elems * itemsize


def fc_flops(fin: int, fout: int, batch: int) -> int:
    return 2 * fin * fout * batch


def least_time_s(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak HBM bytes/s."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def _kind(node) -> str:
    return node.kind.value


def conv_metas(graph) -> List:
    return [n.conv for n in graph.nodes.values() if _kind(n) == "conv"]


def fc_shapes(graph) -> List[tuple]:
    return [(int(n.attrs["in_features"]), int(n.attrs["out_features"]))
            for n in graph.nodes.values() if _kind(n) == "fc"]


def model_flops(graph, batch: int = 1) -> int:
    """Direct FLOPs of one forward pass: every conv and FC layer."""
    return (sum(conv_flops(m, batch) for m in conv_metas(graph))
            + sum(fc_flops(i, o, batch) for i, o in fc_shapes(graph)))


def conv_least_time_s(graph, peak: Dict[str, float], batch: int = 1,
                      itemsize: int = 4) -> float:
    """Sum over conv layers of each layer's least time on its own."""
    return sum(least_time_s(conv_flops(m, batch),
                            conv_bytes(m, batch, itemsize), peak)
               for m in conv_metas(graph))


def totals(graph) -> Dict[str, int]:
    """What a configuration file's ``expect`` block states."""
    convs = conv_metas(graph)
    fcs = fc_shapes(graph)
    params = (sum(m.k1 * m.k2 * m.c_in * m.c_out + m.c_out for m in convs)
              + sum(i * o + o for i, o in fcs))
    macs = (sum(conv_flops(m, 1) // 2 for m in convs)
            + sum(i * o for i, o in fcs))
    return {"convs": len(convs), "params": params, "macs_per_image": macs}


def check_totals(graph, expect: Dict[str, int]) -> None:
    """Raise when the program's graph is not the configuration's model."""
    got = totals(graph)
    bad = {k: (got[k], expect[k]) for k in got if got[k] != expect[k]}
    if bad:
        raise ValueError(f"graph differs from its configuration "
                         f"(got, expected): {bad}")
