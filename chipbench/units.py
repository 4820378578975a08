"""The one conversion from a traffic's payload to a ``CollectiveRequest``.

``CollectiveRequest.bytes`` is the size of one chunk of the synthesized
schedule, in the unit of the fabric's cost model: ``tpu_v5e_pod`` and the
other generators give beta in microseconds per MiB, so the request is in
MiB. A payload passed in bytes is read as that many MiB, which drives the
cost model's times past what its float arithmetic can hold (a 4x8
all-reduce of 32 Mi "MiB" fails validation on a transfer's duration).
"""

from __future__ import annotations

MIB = 1 << 20

# chunks per member that each kind's request describes: an all-gather
# moves each member's whole input as one chunk; the others split a
# member's input into one chunk per member
_CHUNKS_PER_INPUT = {"all_gather": lambda n: 1, "all_reduce": lambda n: n,
                     "reduce_scatter": lambda n: n, "all_to_all": lambda n: n}


def request_mib(kind: str, payload_bytes: int, n: int) -> float:
    """``CollectiveRequest.bytes`` for a ``kind`` collective over ``n``
    members that each hold ``payload_bytes`` of input."""
    try:
        chunks = _CHUNKS_PER_INPUT[kind](n)
    except KeyError:
        raise ValueError(f"no request size for collective {kind!r}") from None
    return payload_bytes / chunks / MIB
