from .grouped import grouped_matmul
from .kernel import default_blocks, vmem_working_set_bytes, zorder_matmul
from .ops import matmul
from .ref import matmul_ref

__all__ = [
    "default_blocks", "vmem_working_set_bytes", "zorder_matmul",
    "matmul", "matmul_ref", "grouped_matmul",
]
