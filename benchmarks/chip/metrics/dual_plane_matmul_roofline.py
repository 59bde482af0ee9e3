"""% of its roofline reached by the dual-plane matmul in decode."""
from readers import dual_matmul_roofline as read  # noqa: F401
