"""Consecutive whole UniProtKB releases as ``.dat`` text, and their plain
reference.

Release 1 holds ``entries`` synthetic entries (accession ``P%08d``,
``seq_len`` residues, a taxon id); every later release rewrites the
sequence and taxon of a ``churn`` share of them, drawn from the seed and
the release number, so that consecutive releases differ in exactly that
share. The text of a release is rendered whole, before it is streamed,
from the text of the release before it with only the churned entries
rendered anew; the reference is the same arrays in the parser's schema.
"""
from __future__ import annotations

import numpy as np

from .history import AA

ANNOT = "RecName: Full=Synthetic protein {i}; | Synthetica gestorensis."


class ReleaseText:
    """Seeded releases of synthetic UniProtKB text, as a base plus edits."""

    def __init__(self, *, entries: int, churn: float, seq_len: int,
                 seq_width: int, annot_width: int, seed: int,
                 entries_per_chunk: int):
        self.n, self.seq_len = entries, seq_len
        self.seq_w, self.annot_w = seq_width, annot_width
        self.churn, self.seed = churn, seed
        self.per_chunk = entries_per_chunk
        rng = np.random.default_rng([seed, 1])
        self.base_seq = AA[rng.integers(0, len(AA), (entries, seq_len),
                                        dtype=np.uint8)]
        self.base_tax = rng.integers(1, 99_999, entries).astype(np.int32)
        annot = np.zeros((entries, annot_width), np.uint8)
        for i in range(entries):
            b = ANNOT.format(i=i).encode()[:annot_width]
            annot[i, :len(b)] = np.frombuffer(b, np.uint8)
        self.annot = annot.view(np.int8)

    def edit(self, t: int):
        """Release t's churn against release t - 1: (rows, sequences,
        taxa), drawn from the seed and t alone."""
        rng = np.random.default_rng([self.seed, t])
        rows = np.sort(rng.choice(self.n, round(self.n * self.churn),
                                  replace=False))
        seq = AA[rng.integers(0, len(AA), (len(rows), self.seq_len),
                              dtype=np.uint8)]
        return rows, seq, rng.integers(1, 99_999, len(rows)).astype(np.int32)

    def raw(self, t: int):
        """(sequence letters, taxa) of release t."""
        seq, tax = self.base_seq.copy(), self.base_tax.copy()
        for r in range(2, t + 1):
            rows, s, x = self.edit(r)
            seq[rows], tax[rows] = s, x
        return seq, tax

    def advance(self, raw, t: int):
        """(sequence letters, taxa) of release t from those of t - 1, and
        the rows that changed; ``raw`` is left as it was."""
        seq, tax = raw[0].copy(), raw[1].copy()
        rows, s, x = self.edit(t)
        seq[rows], tax[rows] = s, x
        return (seq, tax), rows

    def state(self, t: int, raw=None) -> dict:
        """Release t in the parser's schema, every entry alive."""
        seq, tax = raw if raw is not None else self.raw(t)
        s = np.zeros((self.n, self.seq_w), np.uint8)
        s[:, :self.seq_len] = seq
        return {"sequence": s.view(np.int8),
                "length": np.full((self.n, 1), self.seq_len, np.int32),
                "annotation": self.annot, "taxid": tax[:, None],
                "alive": np.ones(self.n, bool)}

    def entries_text(self, seq: np.ndarray, tax: np.ndarray, rows) -> list:
        """The ``.dat`` text of the entries ``rows``, one string each."""
        L = self.seq_len
        out = []
        for i in rows:
            s = seq[i].tobytes().decode("ascii")
            out.append(
                f"ID   E{i:08d}_SYN        Reviewed;       {L} AA.\n"
                f"AC   P{i:08d};\n"
                f"DE   RecName: Full=Synthetic protein {i};\n"
                f"OS   Synthetica gestorensis.\n"
                f"OX   NCBI_TaxID={tax[i]};\n"
                f"SQ   SEQUENCE   {L} AA;  00000 MW;  0000000000000000 CRC64;\n"
                + "".join(f"     {s[j:j + 60]}\n" for j in range(0, L, 60))
                + "//\n")
        return out

    def chunks(self, entries: list) -> list:
        """A release's text, ``entries_per_chunk`` entries a chunk."""
        k = self.per_chunk
        return ["".join(entries[i:i + k]) for i in range(0, len(entries), k)]
