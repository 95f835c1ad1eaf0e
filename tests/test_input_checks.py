"""The engine's input guards: each malformed call is refused with its own
exception and message, before any planning or transfer runs.

One case per guard of ``repro.core`` that no other test reaches: the entry
points (``ooc_gemm``, ``ooc_syrk``, ``ooc_attention``, ``ooc_cholesky``,
``ooc_lu``), the ``hcl`` facade, the partitioner, the pipeline compiler, the
executor and the stream factory.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import (BlockCache, ScheduleExecutor, factor_pipeline_spec,
                        gemm_pipeline_spec, ooc_attention, ooc_cholesky,
                        ooc_gemm, ooc_lu, ooc_syrk, plan_attention_partition,
                        plan_gemm_partition, syrk_pipeline_spec,
                        traversal_order)
from repro.core.api import hclDeviceFactory, hclRuntimeFactory
from repro.core.pipeline import BlockPipelineBuilder
from repro.core.streams import Device, StreamFactory
from repro.fault import FaultPlan
from repro.hybrid import DeviceSpec
from repro.tune.calibrate import gpu_profile

BUDGET = 1 << 20
HOST_ONLY = "host pipeline backend only"


def _mat(m, n):
    return np.random.default_rng(0).standard_normal((m, n)).astype(np.float32)


def _spd(n):
    a = _mat(n, n)
    return a @ a.T + n * np.eye(n, dtype=np.float32)


def _devices():
    return [DeviceSpec("gpu0", gpu_profile(), BUDGET)]


def _part():
    return plan_gemm_partition(256, 256, 64, 1 << 18)


CASES = [
    # ooc_gemm
    pytest.param(lambda: ooc_gemm(_mat(64, 64), _mat(64, 64),
                                  budget_bytes=BUDGET, backend="mesh",
                                  faults=FaultPlan()),
                 ValueError, HOST_ONLY, id="gemm-faults-mesh"),
    pytest.param(lambda: ooc_gemm(_mat(64, 64), _mat(64, 64),
                                  budget_bytes=BUDGET, devices=_devices(),
                                  faults=FaultPlan()),
                 ValueError, HOST_ONLY, id="gemm-faults-devices"),
    pytest.param(lambda: ooc_gemm(_mat(64, 32), _mat(48, 64),
                                  budget_bytes=BUDGET),
                 ValueError, r"inner dims mismatch: \(64, 32\) @ \(48, 64\)",
                 id="gemm-inner-dims"),
    pytest.param(lambda: ooc_gemm(_mat(256, 256), _mat(256, 256),
                                  budget_bytes=1 << 19, backend="disk"),
                 ValueError, "unknown backend 'disk'", id="gemm-backend"),
    # ooc_syrk
    pytest.param(lambda: ooc_syrk(_mat(64, 32), budget_bytes=BUDGET,
                                  tune="grid"),
                 ValueError, "unknown tune mode 'grid'", id="syrk-tune"),
    pytest.param(lambda: ooc_syrk(_mat(64, 32), budget_bytes=BUDGET,
                                  backend="vmem", faults=FaultPlan()),
                 ValueError, HOST_ONLY, id="syrk-faults-vmem"),
    pytest.param(lambda: ooc_syrk(_mat(64, 32), budget_bytes=BUDGET,
                                  devices=_devices(), faults=FaultPlan()),
                 ValueError, HOST_ONLY, id="syrk-faults-devices"),
    pytest.param(lambda: ooc_syrk(_mat(64, 32), budget_bytes=BUDGET,
                                  backend="mesh"),
                 ValueError, "unknown backend 'mesh'", id="syrk-backend"),
    # ooc_attention
    pytest.param(lambda: ooc_attention(_mat(4, 64), np.zeros((256, 2, 64)),
                                       np.zeros((256, 2, 64)),
                                       budget_bytes=BUDGET, tune="grid"),
                 ValueError, "unknown tune mode 'grid'",
                 id="attention-tune"),
    # ooc_cholesky and ooc_lu
    pytest.param(lambda: ooc_cholesky(_spd(64), budget_bytes=BUDGET,
                                      tune="grid"),
                 ValueError, "unknown tune mode 'grid'", id="cholesky-tune"),
    pytest.param(lambda: ooc_cholesky(_spd(64), panel=32, budget_bytes=BUDGET,
                                      backend="vmem", faults=FaultPlan()),
                 ValueError, HOST_ONLY, id="cholesky-faults-vmem"),
    pytest.param(lambda: ooc_lu(_mat(64, 64), budget_bytes=BUDGET,
                                tune="grid"),
                 ValueError, "unknown tune mode 'grid'", id="lu-tune"),
    pytest.param(lambda: ooc_lu(_mat(64, 64), panel=32, budget_bytes=BUDGET,
                                backend="vmem", faults=FaultPlan()),
                 ValueError, HOST_ONLY, id="lu-faults-vmem"),
    pytest.param(lambda: ooc_lu(_mat(64, 64), panel=32, budget_bytes=BUDGET,
                                devices=_devices(), faults=FaultPlan()),
                 ValueError, HOST_ONLY, id="lu-faults-devices"),
    # the hcl facade
    pytest.param(lambda: hclDeviceFactory.create("FPGA"),
                 ValueError, "unknown device type 'FPGA'", id="api-device"),
    pytest.param(lambda: hclRuntimeFactory.create(
                     hclDeviceFactory.create("MESH")),
                 ValueError, "MESH runtime needs a jax Mesh",
                 id="runtime-mesh-without-mesh"),
    # the partitioner
    pytest.param(lambda: traversal_order(0, 3), ValueError, "bad grid 0x3",
                 id="grid-no-rows"),
    pytest.param(lambda: traversal_order(3, 0), ValueError, "bad grid 3x0",
                 id="grid-no-cols"),
    pytest.param(lambda: plan_gemm_partition(64, 0, 64, BUDGET),
                 ValueError, r"bad GEMM shape \(64, 0, 64\)",
                 id="gemm-shape-zero"),
    pytest.param(lambda: plan_gemm_partition(-8, 64, 64, BUDGET),
                 ValueError, r"bad GEMM shape \(-8, 64, 64\)",
                 id="gemm-shape-negative"),
    pytest.param(lambda: plan_gemm_partition(64, 64, 64, 0),
                 ValueError, "budget must be positive", id="budget-zero"),
    pytest.param(lambda: plan_gemm_partition(64, 64, 64, -1),
                 ValueError, "budget must be positive", id="budget-negative"),
    pytest.param(lambda: plan_attention_partition(0, 2, 64, BUDGET),
                 ValueError, "seq_len must be positive", id="seq-len-zero"),
    pytest.param(lambda: plan_attention_partition(-128, 2, 64, BUDGET),
                 ValueError, "seq_len must be positive",
                 id="seq-len-negative"),
    # the pipeline compiler
    pytest.param(lambda: gemm_pipeline_spec(_part()).operand("D"),
                 KeyError, "'D'", id="spec-operand"),
    pytest.param(lambda: BlockCache("A", 2, "fifo", []),
                 ValueError, "unknown eviction policy 'fifo'",
                 id="cache-policy"),
    pytest.param(lambda: BlockCache("A", 0, "lru", []),
                 ValueError, "cache capacity must be >= 1",
                 id="cache-capacity"),
    pytest.param(lambda: gemm_pipeline_spec(_part(), traversal="row",
                                            reuse=False),
                 ValueError, "reuse=False fixes the paper's column-major",
                 id="gemm-naive-traversal"),
    pytest.param(lambda: syrk_pipeline_spec(_part(), traversal="serpentine",
                                            reuse=False),
                 ValueError, "reuse=False fixes the paper's column-major",
                 id="syrk-naive-traversal"),
    pytest.param(lambda: BlockPipelineBuilder(Device("HBM", 0, BUDGET), 0, 2),
                 ValueError, "nbuf and nstreams must be >= 1",
                 id="builder-no-streams"),
    pytest.param(lambda: BlockPipelineBuilder(Device("HBM", 0, BUDGET), 2, 0),
                 ValueError, "nbuf and nstreams must be >= 1",
                 id="builder-no-buffers"),
    pytest.param(lambda: factor_pipeline_spec(0, 128, BUDGET),
                 ValueError, "bad factor shape n=0, panel=128",
                 id="factor-n-zero"),
    pytest.param(lambda: factor_pipeline_spec(512, 0, BUDGET),
                 ValueError, "bad factor shape n=512, panel=0",
                 id="factor-panel-zero"),
    pytest.param(lambda: factor_pipeline_spec(64, 128, 1024),
                 ValueError, r"cholesky panel 64x64 needs \d+B resident, "
                             r"budget is 1024B",
                 id="factor-single-panel-over-budget"),
    pytest.param(lambda: factor_pipeline_spec(1024, 128, 1 << 20, kind="lu",
                                              bm=896, bn=896),
                 ValueError, r"lu pipeline \(panel=128, lookahead=1, "
                             r"bm=896, bn=896\) needs \d+B resident",
                 id="factor-pipeline-over-budget"),
    # the executor and the streams
    pytest.param(lambda: ScheduleExecutor(mode="eager"),
                 ValueError, "unknown executor mode 'eager'",
                 id="executor-mode"),
    pytest.param(lambda: StreamFactory.create(Device("HBM", 0, BUDGET), 0),
                 ValueError, "need at least one stream", id="no-streams"),
]


@pytest.mark.parametrize("call, exc, match", CASES)
def test_malformed_input_is_refused(call, exc, match):
    with pytest.raises(exc, match=match):
        call()
