"""A seeded text with a real vocabulary: Zipf-distributed words in short lines.

The generator of configurations whose ``generator`` block names this module
(``wc-zipf-100MB``), beside ``yardstick.build_corpus``: where that one
shuffles a shipped text of 2,142 distinct words, this one draws every token
from a fixed vocabulary of ``vocab`` words under Zipf's law, so a 100 MB
corpus holds hundreds of thousands of distinct keys — the vocabulary of a
real corpus, which the text itself (enwik8 is not on the machine) is not.

* A word is a function of its rank alone, in lower-case letters.  Its
  LENGTH follows the rank's octave (1 letter for the three commonest words,
  6-9 around rank 200, 15-18 at the millionth), and past rank 32,768 every
  61st word is a long one of 20-32 letters, so keys reach the CLI's
  ``key_width``.  Its LETTERS are the base-26 numeral of the rank itself
  (lengths 1-4) or of ``rank * MULT mod vocab`` in five letters after a
  prefix that varies with the rank (lengths 5-32): words of one length
  differ in those letters, words of two lengths differ in length, so no two
  ranks give one word.
* Ranks are i.i.d. Zipf(``exponent``) TRUNCATED at the vocabulary by the
  inverse CDF — clipping would pile the tail's mass onto the last word
  (``locust_tpu/io/corpus.py`` says the same of its rejection sampler).
* A line holds 3-20 words (uniform) and ends before the word that would
  take it past ``line_bytes``, so no line, key or emit count passes the
  CLI's default widths and nothing is cut.

Everything is numpy over whole arrays; the draw and the line lengths come
from ``numpy.random.default_rng(seed)``.
"""

from __future__ import annotations

import numpy as np

LETTERS = 26
MAX_WORD = 32
NUMERAL = 5                      # letters that hold ``rank * MULT mod vocab``
MULT = 0x9E3779B1                # odd: a permutation of [0, 2^k)
LONG_FROM, LONG_EVERY = 1 << 15, 61
CHUNK_LINES = 1 << 16            # lines drawn at a time: arrays that stay in cache


def word_lengths(vocab: int) -> np.ndarray:
    """Letters in the word of every rank (see the module docstring)."""
    ranks = np.arange(vocab, dtype=np.int64)
    octave = np.floor(np.log2(ranks + 1)).astype(np.int64)
    length = 1 + octave * 3 // 4
    length[octave >= 7] += ranks[octave >= 7] % 4
    long = (ranks >= LONG_FROM) & (ranks % LONG_EVERY == 0)
    length[long] = 20 + (ranks[long] // LONG_EVERY) % (MAX_WORD - 20 + 1)
    return length


def vocabulary(vocab: int):
    """``(flat, offsets)``: the words of ranks 0..vocab-1, each followed by
    one space, concatenated, and where each starts (``offsets[vocab]`` =
    the total): word ``r`` is ``flat[offsets[r]:offsets[r + 1] - 1]``."""
    if vocab & (vocab - 1) or vocab > LETTERS ** NUMERAL:
        raise ValueError(f"vocab must be a power of two <= 26^{NUMERAL}, got {vocab}")
    ranks = np.arange(vocab, dtype=np.int64)
    length = word_lengths(vocab)
    short = length < NUMERAL
    if np.any(ranks[short] >= LETTERS ** length[short]):
        raise ValueError("a short word's rank does not fit its letters")
    offsets = np.concatenate([[0], np.cumsum(length + 1)])
    # Every letter starts as prefix: a function of the rank and of the
    # letter's place, so a long word is not a run of one letter.
    place = np.arange(offsets[-1]) - np.repeat(offsets[:-1], length + 1)
    flat = (ord("a") + (place * 11 + np.repeat(ranks, length + 1) * 7) % LETTERS).astype(np.uint8)
    flat[offsets[1:] - 1] = ord(" ")
    # Then the numeral, least significant letter at the word's end.
    rest = np.where(short, ranks, (ranks * MULT) % vocab)
    for back in range(1, NUMERAL + 1):
        has = length >= back
        flat[offsets[1:][has] - 1 - back] = ord("a") + rest[has] % LETTERS
        rest = rest // LETTERS
    return flat, offsets


def zipf_ranks(rng, n: int, cdf: np.ndarray) -> np.ndarray:
    """``n`` ranks drawn by the inverse of ``cdf`` (unnormalised)."""
    return np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right").clip(max=len(cdf) - 1)


def build(path: str, n_lines: int, seed: int, *, vocab: int, exponent: float,
          words_min: int, words_max: int, line_bytes: int) -> int:
    """Write ``n_lines`` lines to ``path``; returns the bytes written.  The
    text is drawn ``CHUNK_LINES`` lines at a time from one generator, so it
    depends on that constant as it does on the seed."""
    rng = np.random.default_rng(seed)
    flat, offsets = vocabulary(vocab)
    # P(rank r) proportional to (r + 1) ** -exponent, cut at the vocabulary.
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -exponent)
    size = (offsets[1:] - offsets[:-1]).astype(np.int32)     # word + separator
    written = 0
    with open(path, "wb") as f:
        for lo in range(0, n_lines, CHUNK_LINES):
            n = min(CHUNK_LINES, n_lines - lo)
            want = rng.integers(words_min, words_max + 1, size=n)
            first = np.concatenate([[0], np.cumsum(want)])   # a line's first token
            ranks = zipf_ranks(rng, int(first[-1]), cdf)
            tok = size[ranks]
            # A line's bytes up to and including a token, its separator
            # left out: tokens that pass ``line_bytes`` are dropped.
            upto = np.cumsum(tok)
            line_of = np.repeat(np.arange(n), want)
            keep = upto - np.concatenate([[0], upto])[first[:-1]][line_of] - 1 <= line_bytes
            ranks, tok, line_of = ranks[keep], tok[keep], line_of[keep]
            ends = np.cumsum(tok)
            src = np.arange(int(ends[-1]), dtype=np.int64) + np.repeat(
                offsets[ranks] - (ends - tok), tok)
            out = flat[src]
            # A line's last separator is its newline.
            out[ends[np.concatenate([line_of[1:] != line_of[:-1], [True]])] - 1] = ord("\n")
            f.write(out.tobytes())
            written += out.size
    return written
