"""attention_kernel_ms reads the causal attention kernel's device ops
(``splash_mha_<fwd|dq|dkv>...`` custom calls) per upload, and nothing
from a program whose attention runs without it."""
import pytest

import common
from trace_reduce import Reduced


def reduced(ops):
    return Reduced(window_s=1.0, busy_s=sum(ops.values()), chips=1,
                   top_ops=[], idle_gaps=[], op_seconds=ops)


OPS = {"splash_mha_fwd_residuals.1 custom-call": 0.3,
       "splash_mha_fwd_residuals.2 custom-call": 0.3,
       "splash_mha_dq_no_residuals.1 custom-call": 0.5,
       "splash_mha_dkv_no_residuals custom-call": 0.7,
       "fused_server_pallas.1 custom-call": 2.0,
       "splash_mha_fwd_residuals.3 fusion": 9.0,
       "bitcast_add_fusion.36 fusion": 1.0}


@pytest.fixture
def read():
    return common.load_module("metrics", "attention_kernel_ms").read


def test_sums_the_kernel_passes_per_upload(read):
    run = {"trace": reduced(OPS), "counts": {"rounds": 3}}
    assert read(run) == pytest.approx(1e3 * 1.8 / 3)


@pytest.mark.parametrize("trace,uploads", [
    # the chunked XLA attention: no splash call
    ({"bitcast_add_fusion.36 fusion": 1.0,
      "multiply_reduce_fusion.112 fusion": 0.6}, 3),
    (OPS, 0),
    (None, 3),
])
def test_reads_nothing_without_the_kernel(read, trace, uploads):
    run = {"trace": None if trace is None else reduced(trace),
           "counts": {"rounds": uploads}}
    assert read(run) is None
