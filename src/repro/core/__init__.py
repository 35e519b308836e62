"""Core: the paper's contribution — BP-im2col implicit backprop lowering."""

from repro.core.im2col_ref import ConvDims
from repro.core.convspec import (ConvSpec, ConvTransposeSpec, EnginePolicy,
                                 PASSES)
from repro.core.conv import (MODES, conv1d, conv1d_causal, conv2d,
                             conv2d_transpose,
                             conv2d_transpose_materialized,
                             conv_policy, conv_transpose_output_shape,
                             depthwise_causal_conv1d,
                             dispatch_events, make_dims, policy_decisions,
                             policy_report, register_engine,
                             reset_dispatch_events, resolve_policy,
                             runtime_failures, spec_dims, transpose_dims,
                             transpose_tap_counts)

__all__ = ["ConvDims", "ConvSpec", "ConvTransposeSpec", "EnginePolicy",
           "PASSES", "MODES",
           "conv2d", "conv2d_transpose", "conv2d_transpose_materialized",
           "conv1d", "conv1d_causal", "depthwise_causal_conv1d",
           "conv_policy", "conv_transpose_output_shape", "dispatch_events",
           "policy_decisions", "reset_dispatch_events", "resolve_policy",
           "runtime_failures",
           "policy_report", "register_engine", "make_dims", "spec_dims",
           "transpose_dims", "transpose_tap_counts"]
