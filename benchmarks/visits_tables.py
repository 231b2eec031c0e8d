"""Two seeded tables at Pavlo et al.'s schema: Rankings and UserVisits.

The generator of ``join-visits-1M`` (HiBench ``sql/join``, the Join Task of
"A Comparison of Approaches to Large-Scale Data Analysis", SIGMOD 2009,
section 4.3.4).  Neither HiBench's generator nor its output is on this
machine, so both files are drawn here from ``--seed`` by the schema's laws,
written out below and in the configuration's ``generator`` block; each is
listed under the configuration's ``assumed`` with what it hides.

    Rankings  (pageURL VARCHAR(100) PRIMARY KEY, pageRank INT, avgDuration INT)
    UserVisits(sourceIP VARCHAR(16), destURL VARCHAR(100), visitDate DATE,
               adRevenue FLOAT, userAgent VARCHAR(64), countryCode VARCHAR(3),
               languageCode VARCHAR(6), searchWord VARCHAR(32), duration INT)

Both are text, one row a line, fields apart by ``,`` in the schema's order
(HiBench's Hive tables: ``FIELDS TERMINATED BY ','``); no field holds a
``,``, no UserVisits line exceeds 255 bytes.

* Rankings, ``pages`` rows.  ``pageURL``: ``http://`` and characters of
  ``a-z0-9./-``, ``url_min .. url_max`` bytes in all (uniform), the last
  five a ``/`` and the page's number in base 36 — unique by construction.
  ``pageRank``: the page's in-link count under Zipf's law (exponent 1):
  ``max(1, rank_max // k)`` for the page at place k of a random order.
  ``avgDuration``: uniform 1-100.
* UserVisits, ``visits`` rows.  ``destURL``: a Rankings URL, its page drawn
  Zipf(``url_exponent``) over ANOTHER random order of the pages (a hot page
  is not a high-ranked one by construction).  ``sourceIP``: a dotted quad
  drawn Zipf(``ip_exponent``) from a pool of ``ip_pool`` distinct
  addresses.  ``visitDate``: uniform over ``date_first .. date_last`` as
  ``YYYY-MM-DD``.  ``adRevenue``: uniform in [0, 1000), six decimals.
  ``userAgent`` 20-64 bytes, ``countryCode`` 3, ``languageCode`` 5-6,
  ``searchWord`` 3-32 (letters), ``duration`` 1-10000.

Everything is numpy over whole arrays: a table is assembled as one matrix
of NUL-padded fields a line and its NULs dropped.  The draws come from
``numpy.random.default_rng(seed)``; UserVisits is written ``CHUNK`` rows at
a time from that one generator, so it depends on that constant as it does
on the seed.
"""

from __future__ import annotations

import datetime

import numpy as np

URL_CHARS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz0123456789./-", np.uint8)
LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)
AGENT_CHARS = np.frombuffer(
    b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 /.;()", np.uint8)
BASE36 = np.frombuffer(b"0123456789abcdefghijklmnopqrstuvwxyz", np.uint8)
SCHEME = np.frombuffer(b"http://", np.uint8)
ID_CHARS = 4        # base-36 digits of a page's number: 36^4 pages at most
CHUNK = 1 << 17     # UserVisits rows drawn at a time
COMMA, LF = ord(","), ord("\n")


def _text(rng, n: int, lo: int, hi: int, alphabet: np.ndarray) -> np.ndarray:
    """``[n, hi]`` random characters of ``alphabet``, each row's length
    uniform in ``lo .. hi``, NUL past it."""
    length = rng.integers(lo, hi + 1, size=n)
    chars = alphabet[rng.integers(0, alphabet.size, size=(n, hi), dtype=np.uint8)]
    chars[np.arange(hi)[None, :] >= length[:, None]] = 0
    return chars


def _digits(values: np.ndarray, width: int, pad: bool = False) -> np.ndarray:
    """``[n, width]`` decimal digits, most significant first; without
    ``pad`` the leading zeros are NUL (the last digit always stands)."""
    v = values.astype(np.int64)
    out = np.empty((v.size, width), np.uint8)
    for col in range(width - 1, -1, -1):
        out[:, col] = v % 10 + ord("0")
        v = v // 10
    if not pad:
        lead = np.cumsum(out != ord("0"), axis=1) == 0
        lead[:, -1] = False
        out[lead] = 0
    return out


def _zipf_cdf(items: int, exponent: float) -> np.ndarray:
    """Unnormalised: P(k) proportional to (k + 1) ** -exponent, k < items."""
    return np.cumsum(np.arange(1, items + 1, dtype=np.float64) ** -exponent)


def _zipf(rng, n: int, cdf: np.ndarray) -> np.ndarray:
    """``n`` draws by the inverse of ``cdf``."""
    return np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right").clip(max=cdf.size - 1)


def _lines(fields: list[np.ndarray]) -> np.ndarray:
    """Rows of NUL-padded fields -> the table's bytes: a ``,`` between two
    fields, a LF after the last, the NULs dropped."""
    n = fields[0].shape[0]
    parts = []
    for i, field in enumerate(fields):
        parts.append(field)
        parts.append(np.full((n, 1), LF if i == len(fields) - 1 else COMMA, np.uint8))
    flat = np.concatenate(parts, axis=1).ravel()
    return flat[flat != 0]


def page_urls(rng, pages: int, url_min: int, url_max: int) -> np.ndarray:
    """``[pages, url_max]`` NUL-padded URLs, unique: see the module docstring."""
    if pages > 36 ** ID_CHARS:
        raise ValueError(f"{pages} pages do not fit {ID_CHARS} base-36 digits")
    if url_min < SCHEME.size + ID_CHARS + 2:
        raise ValueError(f"url_min {url_min} leaves no room for the page's number")
    urls = _text(rng, pages, url_min, url_max, URL_CHARS)
    length = np.count_nonzero(urls, axis=1)
    urls[:, :SCHEME.size] = SCHEME
    row = np.arange(pages)
    number = row.copy()
    for back in range(1, ID_CHARS + 1):
        urls[row, length - back] = BASE36[number % 36]
        number //= 36
    urls[row, length - ID_CHARS - 1] = ord("/")
    return urls


def build(rankings_path: str, visits_path: str, seed: int, *, pages: int, visits: int,
          url_min: int, url_max: int, rank_max: int, url_exponent: float,
          ip_pool: int, ip_exponent: float, date_first: str, date_last: str) -> tuple[int, int]:
    """Write both tables; returns ``(Rankings bytes, UserVisits bytes)``."""
    rng = np.random.default_rng(seed)
    urls = page_urls(rng, pages, url_min, url_max)
    place = rng.permutation(pages) + 1
    ranks = np.maximum(1, rank_max // place)
    table = _lines([urls, _digits(ranks, 6), _digits(rng.integers(1, 101, size=pages), 3)])
    with open(rankings_path, "wb") as f:
        f.write(table.tobytes())
    written = [int(table.size), 0]

    hot = rng.permutation(pages)  # the visits' order of the pages: not the ranks'
    octets = np.unique(rng.integers(0, 1 << 32, size=ip_pool * 2, dtype=np.uint64))
    if octets.size < ip_pool:
        raise ValueError(f"drew only {octets.size} distinct addresses of {ip_pool}")
    octets = rng.permutation(octets)[:ip_pool]
    dot = np.full((ip_pool, 1), ord("."), np.uint8)
    quad = [_digits((octets >> np.uint64(shift)) & np.uint64(255), 3) for shift in (24, 16, 8, 0)]
    pool = np.concatenate([quad[0], dot, quad[1], dot, quad[2], dot, quad[3]], axis=1)
    day0 = datetime.date.fromisoformat(date_first)
    n_days = (datetime.date.fromisoformat(date_last) - day0).days + 1
    days = np.frombuffer(
        b"".join((day0 + datetime.timedelta(d)).isoformat().encode() for d in range(n_days)),
        np.uint8).reshape(n_days, 10)
    ip_cdf, url_cdf = _zipf_cdf(ip_pool, ip_exponent), _zipf_cdf(pages, url_exponent)
    with open(visits_path, "wb") as f:
        for lo in range(0, visits, CHUNK):
            n = min(CHUNK, visits - lo)
            point = np.full((n, 1), ord("."), np.uint8)
            revenue = rng.integers(0, 1000 * 10 ** 6, size=n)
            chunk = _lines([
                pool[_zipf(rng, n, ip_cdf)],
                urls[hot[_zipf(rng, n, url_cdf)]],
                days[rng.integers(0, n_days, size=n)],
                np.concatenate([_digits(revenue // 10 ** 6, 3), point,
                                _digits(revenue % 10 ** 6, 6, pad=True)], axis=1),
                _text(rng, n, 20, 64, AGENT_CHARS),
                _text(rng, n, 3, 3, LETTERS),
                _text(rng, n, 5, 6, LETTERS),
                _text(rng, n, 3, 32, LETTERS),
                _digits(rng.integers(1, 10001, size=n), 5),
            ])
            f.write(chunk.tobytes())
            written[1] += int(chunk.size)
    return written[0], written[1]
