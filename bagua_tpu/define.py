"""Shared typed definitions.

Counterpart of the reference's ``bagua/bagua_define.py`` (TensorDeclaration :18,
BaguaHyperparameter :34, BaguaCoreTelemetrySpan :53).  Same wire shape so the
autotune HTTP protocol stays compatible.
"""

from __future__ import annotations

import enum
from typing import List

from pydantic import BaseModel


class TensorDtype(str, enum.Enum):
    F32 = "f32"
    F16 = "f16"
    BF16 = "bf16"
    U8 = "u8"
    I32 = "i32"
    I64 = "i64"


DTYPE_BYTES = {
    TensorDtype.F32: 4,
    TensorDtype.F16: 2,
    TensorDtype.BF16: 2,
    TensorDtype.U8: 1,
    TensorDtype.I32: 4,
    TensorDtype.I64: 8,
}


class TensorDeclaration(BaseModel):
    name: str
    num_elements: int
    dtype: TensorDtype

    def __hash__(self):  # used in ordering / dedup
        return hash((self.name, self.num_elements, self.dtype))

    @property
    def nbytes(self) -> int:
        return self.num_elements * DTYPE_BYTES[TensorDtype(self.dtype)]


def get_tensor_declaration_bytes(td: TensorDeclaration) -> int:
    return td.nbytes


class BaguaHyperparameter(BaseModel):
    """Tunable hyperparameters mutated by the autotune service
    (reference bagua_define.py:34-50)."""

    buckets: List[List[TensorDeclaration]] = []
    is_hierarchical_reduce: bool = False
    bucket_size: int = 10 * 1024 ** 2
    #: algorithm family recommended by the autotuner ("" = keep current);
    #: TPU extension over the reference — the north star wants the
    #: centralized/decentralized/low-precision families to be selectable
    algorithm: str = ""
    #: overlap-scheduler dispatch gate ("auto"|"on"|"off"; "" = keep
    #: current) — rides the recommendation path so re-bucketing and
    #: overlap tuning compose (TPU extension, ISSUE 2)
    overlap: str = ""
    #: chunked-ring sub-collective size in bytes (0 = keep current)
    overlap_chunk_bytes: int = 0
    #: per-bandwidth-tier chunk targets for hierarchical two-level
    #: collectives (docs/hierarchical.md): the slice-local ICI stages and
    #: the cross-slice DCN stage size their ring chunks against different
    #: bytes (0 = keep current / fall back to ``overlap_chunk_bytes``)
    overlap_chunk_bytes_intra: int = 0
    overlap_chunk_bytes_inter: int = 0
    #: per-link-class codec policy (docs/compression.md): what the ring
    #: hops of each bandwidth tier carry on the wire — ``off``/``auto``/a
    #: codec name ("" = keep current).  ``compress_inter`` is the knob the
    #: autopilot's ``compress_dcn`` trend hint actuates through the
    #: recommendation path (compress the slow link when DCN seconds
    #: dominate the step)
    compress_intra: str = ""
    compress_inter: str = ""
    #: bucket-flat residency of the training state ("on"|"off"; "" = keep
    #: current).  A live flip queues a flat<->leaf state migration on the
    #: trainer (same conversion the checkpoint path uses), so the v2
    #: search can trade the per-step flatten against relayout cost
    flat_resident: str = ""

    def update(self, param_dict: dict) -> "BaguaHyperparameter":
        tmp = self.model_dump()
        tmp.update(param_dict)
        for key, value in param_dict.items():
            if key in tmp:
                self.__dict__[key] = value
        return self


class BaguaCoreTelemetrySpan(BaseModel):
    trace_id: int
    action: str
    tensor_name: str
    start_time: int
    end_time: int
