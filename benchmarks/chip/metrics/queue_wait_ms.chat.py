"""Mean ms from due to admitted into a row, over the requests due in
the window."""
from readers import queue_wait_ms as read  # noqa: F401
