"""The simulator kernels the per-layer metrics read: how each one is named in
the device trace, and the least bytes an engine call of it must move.

The kernel is a sequential walk over the trace with its state in fast
memory, so the bound that applies is memory traffic: every access's input
words read once and its output words written once, and the carried state
read and written once per engine call.  Nothing is counted for the state's
padding or for rereads, so the roofline share read from these counts is a
lower bound of the true one.

A kernel's device time is that of its engine-call program: the jitted
Pallas call with the relayout of its carried state, named after the jitted
function on the profiler's ``XLA Modules`` line.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"

WORD = 4  # bytes of an int32 word

# Per simulated (config x access): six int32 (set, tag) key words in, one
# packed hit word out.
SYSTEM_SIM_WORDS = 7

PROGRAMS = {
    "system_sim": r"system_sim_batched_pallas",
}


def call_bytes(call: dict) -> int:
    """Least bytes one engine call moves: ``call`` records ``kernel``,
    ``work`` (simulated configuration x access pairs) and ``state_words``
    (words of carried state of the whole batch)."""
    words = {"system_sim": SYSTEM_SIM_WORDS}[call["kernel"]]
    return WORD * (words * call["work"] + 2 * call["state_words"])


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a device missing from
    ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]
