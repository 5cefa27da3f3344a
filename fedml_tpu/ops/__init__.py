"""Hand formulations of the hot ops.

The compute path is mostly XLA-fused jnp; this package holds the ops where a
hand formulation beats what XLA finds on its own: blockwise flash attention
as Pallas kernels (forward + backward), the inner loop of the TransformerLM
and of ring attention's per-device block update; and ``packed_conv``, the
width-packed 3x3 convolution the CIFAR ResNet hands to ``nn.Conv`` (an XLA
convolution still, of another shape).
"""

from fedml_tpu.ops.flash_attention import flash_attention

__all__ = ["flash_attention"]
