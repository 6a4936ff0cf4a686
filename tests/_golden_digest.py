"""Literal digests for the serving golden pins.

A pin compares a run against a hex string written into the test, not
against a second run of the program, so it still catches a change when
every serving entry point shares one loop.  ``trace_digest`` hashes every
column's dtype, shape and bytes plus the ``repr`` of each scalar field (the
trace meta included); ``report_digest`` hashes the ``repr`` of every
:class:`~repro.serve.ServeReport` field, ``sim`` included; ``json_digest``
hashes a Chrome-trace document with sorted keys.
"""

import dataclasses
import hashlib
import json

import numpy as np


def trace_digest(trace) -> str:
    h = hashlib.sha256()
    for f in dataclasses.fields(trace):
        value = getattr(trace, f.name)
        h.update(f.name.encode())
        if isinstance(value, np.ndarray):
            h.update(f"{value.dtype.str}{value.shape}".encode())
            h.update(np.ascontiguousarray(value).tobytes())
        else:
            h.update(repr(value).encode())
    return h.hexdigest()


def report_lines(report) -> str:
    return "\n".join(f"{f.name}={getattr(report, f.name)!r}"
                     for f in dataclasses.fields(report))


def report_digest(report) -> str:
    return hashlib.sha256(report_lines(report).encode()).hexdigest()


def json_digest(doc: dict) -> str:
    return hashlib.sha256(json.dumps(doc, sort_keys=True).encode()).hexdigest()
