"""% of its roofline reached by the paged decode-attention kernel."""
from readers import paged_attention_roofline as read  # noqa: F401
