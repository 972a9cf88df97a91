"""Sequence packing for LM pretraining batches.

Multiple documents share one fixed-length row with ``segment_ids``
marking document membership (ids start at 1; 0 is padding).  The model
side (``models/transformer.py``) masks attention and positions per
segment, and ``packed_token_cross_entropy`` excludes cross-document
and padding targets — so a packed batch computes exactly the loss the
same documents would produce unpacked, at a fraction of the padding
waste.  The reference has no LM/data story (Horovod sits below the
model); this is the TPU-native throughput lever for the GPT bench:
static shapes (XLA-friendly), no dynamic padding buckets.
"""

from __future__ import annotations

from typing import Iterable, List, Sequence, Tuple

import numpy as np


def pack_documents(
    docs: Sequence[np.ndarray],
    seq_len: int,
    pad_id: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Greedy first-fit packing of token arrays into ``(rows, seq_len)``.

    Returns ``(tokens, segment_ids)`` int32 arrays of identical shape.
    Documents longer than ``seq_len`` are split into ``seq_len`` chunks
    (standard LM practice — each chunk becomes its own segment).
    Segment ids are unique per (row, document) starting at 1; padding
    positions carry segment id 0 and ``pad_id`` tokens.  No documents
    (or only zero-length ones) yield empty ``(0, seq_len)`` arrays —
    never a phantom all-padding row, which would dilute loss masks and
    batch statistics downstream.
    """
    if seq_len <= 0:
        raise ValueError(f"seq_len must be positive, got {seq_len}")
    pieces: List[np.ndarray] = []
    for d in docs:
        d = np.asarray(d).reshape(-1)
        for lo in range(0, len(d), seq_len):
            piece = d[lo:lo + seq_len]
            if len(piece):
                pieces.append(piece)
    # First-fit decreasing: sort longest-first for tighter rows.
    order = sorted(range(len(pieces)), key=lambda i: -len(pieces[i]))
    rows: List[List[np.ndarray]] = []
    space: List[int] = []
    for i in order:
        piece = pieces[i]
        for r in range(len(rows)):
            if space[r] >= len(piece):
                rows[r].append(piece)
                space[r] -= len(piece)
                break
        else:
            rows.append([piece])
            space.append(seq_len - len(piece))
    n = len(rows)
    tokens = np.full((n, seq_len), pad_id, np.int32)
    segs = np.zeros((n, seq_len), np.int32)
    for r, row in enumerate(rows):
        off = 0
        for s, piece in enumerate(row, start=1):
            tokens[r, off:off + len(piece)] = piece
            segs[r, off:off + len(piece)] = s
            off += len(piece)
    return tokens, segs


def pack_batches(
    docs: Iterable[np.ndarray],
    seq_len: int,
    batch_size: int,
    pad_id: int = 0,
    drop_remainder: bool = True,
):
    """Yield ``(tokens, segment_ids)`` batches of shape
    ``(batch_size, seq_len)`` from a document stream (static shapes for
    jit).  Rows pack greedily within a window of documents.

    The packer's own work on a window — :func:`pack_documents` and the
    stacking of the full batches its rows complete — is one
    ``pack_window`` span (``hvd_pack_window`` in a profile, on the
    thread that packs; docs/tracing.md).  It is closed before the
    batches are yielded, so neither the pull of a document from
    ``docs`` nor the time the generator stands suspended is in it."""
    from .. import trace

    rows_t: List[np.ndarray] = []  # packed rows no batch has taken yet
    rows_s: List[np.ndarray] = []

    def pack_window(window, tokens) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Pack ``window`` behind the rows left over and cut off the
        full batches."""
        with trace.span(
            "pack_window", "input", docs=len(window), tokens=tokens,
        ) as span:
            t, s = pack_documents(window, seq_len, pad_id)
            if span is not None:  # known only now; None at level off
                span.attrs["rows"] = len(t)
            rows_t.extend(t)
            rows_s.extend(s)
            batches = []
            while len(rows_t) >= batch_size:
                batches.append((np.stack(rows_t[:batch_size]),
                                np.stack(rows_s[:batch_size])))
                del rows_t[:batch_size], rows_s[:batch_size]
        return batches

    # Pack in windows big enough to fill ~2 batches so first-fit has
    # material to work with, then emit full batches.
    window: List[np.ndarray] = []
    held = 0  # tokens of the window's documents
    for d in docs:
        window.append(np.asarray(d).reshape(-1))
        held += len(window[-1])
        if held >= 2 * batch_size * seq_len:
            yield from pack_window(window, held)
            window, held = [], 0
    if window:
        yield from pack_window(window, held)
    if rows_t and not drop_remainder:
        pad_rows = batch_size - len(rows_t)
        t = np.concatenate(
            [np.stack(rows_t),
             np.full((pad_rows, seq_len), pad_id, np.int32)]
        )
        s = np.concatenate(
            [np.stack(rows_s), np.zeros((pad_rows, seq_len), np.int32)]
        )
        yield t, s


def packing_efficiency(segment_ids: np.ndarray) -> float:
    """Fraction of non-padding positions (1.0 = zero waste)."""
    segs = np.asarray(segment_ids)
    return float((segs > 0).mean()) if segs.size else 0.0
