"""% of the traced window in which the device ran no operation."""
from readers import idle_share as read  # noqa: F401
