"""apex_tpu.normalization — fused normalization layers
(reference ``apex/normalization/__init__.py`` exports ``FusedLayerNorm`` and
``FusedRMSNorm``)."""

from apex_tpu.normalization.fused_layer_norm import (
    FusedLayerNorm,
    FusedRMSNorm,
    fused_layer_norm,
    fused_layer_norm_affine,
    fused_rms_norm_affine,
)

__all__ = ["FusedLayerNorm", "FusedRMSNorm", "fused_layer_norm",
           "fused_layer_norm_affine", "fused_rms_norm_affine"]
