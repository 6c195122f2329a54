"""Oracle DES: a faithful single-threaded discrete-event simulator matching
the reference engine's semantics bit-for-bit (same java.util.Random stream,
same per-ms LIFO delivery order, same per-destination jitter hashing).

This is the parity oracle prescribed by SURVEY.md §7 step 2: every batched
TPU kernel is validated against it, first for exact semantics on small runs,
then distributionally (CDF ±1%) at scale.  It is also the debug runner and
the backend for the REST server's interactive mode.
"""

from .messages import (
    ConditionalTask,
    FloodMessage,
    Message,
    PeriodicTask,
    SendMessage,
    StatusFloodMessage,
    Task,
)
from .network import EnvelopeInfo, Network, Protocol

__all__ = [
    "ConditionalTask",
    "EnvelopeInfo",
    "FloodMessage",
    "Message",
    "Network",
    "PeriodicTask",
    "Protocol",
    "SendMessage",
    "StatusFloodMessage",
    "Task",
]
