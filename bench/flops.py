"""Operations and bytes of one conv pass, from the layer's own geometry.

Every pass (forward, input gradient, weight gradient) of a conv does the
dense conv's multiply-adds once: ``B * N * H_o * W_o * C * K * K`` for a
regular conv, and for a transposed conv those of its mirror regular conv
(each input pixel meets ``K * K`` taps of every output channel).  Zeros
that a lowering would insert are not counted: they are not useful work.

Bytes are the compact traffic of a pass: the activations it reads, the
compact weight and the tensor it writes, each once, at the operand width.
The same three tensors meet in every pass (forward reads x and w and
writes y; input grad reads dy and w and writes dx; weight grad reads x and
dy and writes dw), so the count is the same for all three.  Padding to
tiles or lanes is not counted, so it shows as lost roofline share.
"""

from __future__ import annotations

import dataclasses

PASSES = ("forward", "input_grad", "weight_grad")


@dataclasses.dataclass(frozen=True)
class Conv:
    """An NCHW conv layer: input (B, C, H, H), C -> N channels, a K x K
    kernel, stride S, padding P (and ``out_pad`` extra rows and columns on
    the high side of a transposed conv's output)."""

    B: int
    C: int
    H: int
    N: int
    K: int
    S: int
    P: int
    transposed: bool = False
    out_pad: int = 0

    @property
    def H_o(self) -> int:
        if self.transposed:
            return (self.H - 1) * self.S - 2 * self.P + self.K + self.out_pad
        return (self.H + 2 * self.P - self.K) // self.S + 1

    def macs(self) -> int:
        if self.transposed:
            return self.B * self.C * self.H * self.H * self.N * self.K ** 2
        return self.B * self.N * self.H_o ** 2 * self.C * self.K ** 2

    def elems(self) -> int:
        """Elements of input, weight and output together."""
        return (self.B * self.C * self.H ** 2 + self.N * self.C * self.K ** 2
                + self.B * self.N * self.H_o ** 2)


def pass_flops(conv: Conv) -> int:
    return 2 * conv.macs()


def pass_bytes(conv: Conv, itemsize: int = 4) -> int:
    return conv.elems() * itemsize


def least_seconds(conv: Conv, peak: dict, itemsize: int = 4) -> float:
    """The least time one pass can take on a chip with ``peak``: the larger
    of its operations at the peak rate and its bytes at HBM bandwidth."""
    return max(pass_flops(conv) / peak["bf16_flops_per_s"],
               pass_bytes(conv, itemsize) / peak["hbm_bytes_per_s"])
