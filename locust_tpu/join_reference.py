"""The plain reference of the join: what ``python -m locust_tpu join
RANKINGS USERVISITS`` must print, computed the straightforward way on the
host.

Independent of the code under test: a ``dict``, a loop, ``datetime.date``
and float64 — no jax, no hashing, no blocks, no widths, nothing of
``locust_tpu`` (it lies beside ``index_reference.py`` and
``pagerank_reference.py``, outside ``apps/``, whose package imports jax).
The semantics are HiBench's ``sql/join`` — the Join Task of Pavlo et al.,
SIGMOD 2009, section 4.3.4 — to the letter of ``apps/join.py``:

    SELECT sourceIP, avg(pageRank), sum(adRevenue) AS totalRevenue
    FROM rankings R JOIN
         (SELECT sourceIP, destURL, adRevenue FROM uservisits UV
          WHERE UV.visitDate >= FROM AND UV.visitDate <= TO) NUV
      ON (R.pageURL = NUV.destURL)
    GROUP BY sourceIP ORDER BY totalRevenue DESC

* both files are text, one row a line, fields apart by ``,`` in the
  schema's order (Rankings ``pageURL,pageRank,avgDuration``; UserVisits
  ``sourceIP,destURL,visitDate,adRevenue,...``); an empty line is no row;
* a Rankings row needs two fields, the second an integer of one to nine
  digits; a UserVisits row needs four, the third a date ``YYYY-MM-DD`` of
  the calendar (``datetime.date``'s), the fourth a decimal of one to nine
  digits and, after a point, one to six more.  A row that does not parse
  is MALFORMED: counted, and no part of the result;
* ``pageURL`` is a primary key; of a URL listed twice the last row stands
  (a ``dict``'s assignment);
* a visit passes if ``FROM <= visitDate <= TO``, both ends in; a passed
  visit whose ``destURL`` is no page's, and a page nobody visits in the
  window, are dropped (an inner join) and counted;
* the result gives every sourceIP with a visit that passed and matched,
  once: the mean of its visits' page ranks and the sum of their revenues
  (float64), ordered by the sum descending, ties by the sourceIP's bytes
  ascending;
* printed, that is one ``sourceIP<TAB>avgPageRank<TAB>totalRevenue<LF>``
  line each, the two numbers with nine significant digits as
  ``d.dddddddde+XX``.

The benchmark keeps its own copy (``benchmarks/join_oracle.py``).
"""

from __future__ import annotations

import dataclasses
import datetime
import re

_DATE = re.compile(rb"(\d{4})-(\d{2})-(\d{2})")
_RANK = re.compile(rb"\d{1,9}")
_REVENUE = re.compile(rb"\d{1,9}(\.\d{1,6})?")


@dataclasses.dataclass
class Table:
    rows: list        # [(sourceIP bytes, avgPageRank, totalRevenue)], ordered
    pages: int        # lines of Rankings
    visits: int       # lines of UserVisits
    passed: int       # well-formed visits inside the window
    matched: int      # those of them whose destURL is a page's
    pages_visited: int
    malformed: int    # rows of either file that do not parse


def file_lines(path: str) -> list[bytes]:
    """The file's lines: split at LF, a last line without one counted, a CR
    before the LF no part of the line."""
    with open(path, "rb") as f:
        data = f.read()
    lines = data.split(b"\n")
    if lines and lines[-1] == b"":
        lines.pop()
    return [ln[:-1] if ln.endswith(b"\r") else ln for ln in lines]


def parse_page(line: bytes):
    """``(pageURL, pageRank)`` of a Rankings line, or None."""
    fields = line.split(b",")
    if len(fields) < 2 or not _RANK.fullmatch(fields[1]):
        return None
    return fields[0], int(fields[1])


def parse_visit(line: bytes):
    """``(sourceIP, destURL, visitDate, adRevenue)`` of a UserVisits line,
    or None."""
    fields = line.split(b",")
    if len(fields) < 4:
        return None
    day = _DATE.fullmatch(fields[2])
    if not day or not _REVENUE.fullmatch(fields[3]):
        return None
    try:
        date = datetime.date(*map(int, day.groups()))
    except ValueError:
        return None
    return fields[0], fields[1], date, float(fields[3])


def join(rankings: list[bytes], uservisits: list[bytes],
         date_from: str = "1999-01-01", date_to: str = "2000-01-01") -> Table:
    first = datetime.date.fromisoformat(date_from)
    last = datetime.date.fromisoformat(date_to)
    malformed = 0
    rank_of: dict[bytes, int] = {}
    for line in rankings:
        if not line:
            continue
        page = parse_page(line)
        if page is None:
            malformed += 1
        else:
            rank_of[page[0]] = page[1]
    passed = matched = 0
    visited = set()
    groups: dict[bytes, list] = {}
    for line in uservisits:
        if not line:
            continue
        visit = parse_visit(line)
        if visit is None:
            malformed += 1
            continue
        ip, url, date, revenue = visit
        if not first <= date <= last:
            continue
        passed += 1
        if url not in rank_of:
            continue
        matched += 1
        visited.add(url)
        group = groups.setdefault(ip, [0.0, 0.0, 0])
        group[0] += revenue
        group[1] += rank_of[url]
        group[2] += 1
    rows = sorted(
        ((ip, ranks / n, total) for ip, (total, ranks, n) in groups.items()),
        key=lambda row: (-row[2], row[0]))
    return Table(rows=rows, pages=len(rankings), visits=len(uservisits),
                 passed=passed, matched=matched, pages_visited=len(visited),
                 malformed=malformed)


def render(rows) -> bytes:
    return b"".join(
        ip + f"\t{avg:.8e}\t{total:.8e}\n".encode() for ip, avg, total in rows)


def parse(table: bytes) -> list:
    """A printed table back into ``[(sourceIP, avgPageRank, totalRevenue)]``;
    raises ``ValueError`` where a line is not ``ip<TAB>number<TAB>number``."""
    rows = []
    for line in table.split(b"\n")[:-1] if table else []:
        ip, avg, total = line.split(b"\t")
        rows.append((ip, float(avg), float(total)))
    if table and not table.endswith(b"\n"):
        raise ValueError("the table's last line has no end")
    return rows
