"""HiBench's ``sql/join`` over two text tables: the oracle of
``join-visits-1M``.

The benchmark's OWN copy, as ``index_oracle.py`` holds the index's and
``rmat_edges.py`` PageRank's: nothing here imports the program or jax, so
no later PR can move the measure by editing ``locust_tpu/``
(``locust_tpu/join_reference.py`` is the program's copy of the same
semantics, and ``benchmarks/tests/test_join_cell.py`` holds the two equal).

The query (Pavlo et al., SIGMOD 2009, section 4.3.4, as HiBench runs it):

    SELECT sourceIP, avg(pageRank), sum(adRevenue) AS totalRevenue
    FROM rankings R JOIN
         (SELECT sourceIP, destURL, adRevenue FROM uservisits UV
          WHERE UV.visitDate >= FROM AND UV.visitDate <= TO) NUV
      ON (R.pageURL = NUV.destURL)
    GROUP BY sourceIP ORDER BY totalRevenue DESC

in straightforward Python: a ``dict`` of pageURL to pageRank, a loop over
the UserVisits lines split on ``,``, ``datetime.date`` comparison, float64
sums, ``sorted`` (ties by the sourceIP's bytes).  A Rankings row needs two
fields, the second an integer of one to nine digits; a UserVisits row
four, the third a calendar date ``YYYY-MM-DD``, the fourth a decimal of one
to nine digits and at most six places; a row that does not parse is
malformed — counted, no part of the result.  An empty line is no row.

``Oracle`` keeps what the driver compares — the rows, and their rendering
with nine significant digits — and what the roofline prices: the DATA's
counts (lines, bytes, passed, matched, groups, pages visited).
"""

from __future__ import annotations

import dataclasses
import datetime
import re

_DATE = re.compile(rb"(\d{4})-(\d{2})-(\d{2})")
_RANK = re.compile(rb"\d{1,9}")
_REVENUE = re.compile(rb"\d{1,9}(\.\d{1,6})?")


@dataclasses.dataclass
class Oracle:
    rows: list          # [(sourceIP, avgPageRank, totalRevenue)], ordered
    table: bytes        # the rows as the CLI prints them
    pages: int          # lines of Rankings
    visits: int         # lines of UserVisits
    bytes: int          # of both files
    passed: int         # well-formed visits inside the window
    matched: int        # those of them whose destURL is a page's
    groups: int         # sourceIPs in the result
    largest_group: int  # visits of the sourceIP with most of them
    pages_visited: int  # distinct pages the matched visits name
    malformed: int

    def counts(self) -> dict:
        return {k: getattr(self, k) for k in (
            "pages", "visits", "bytes", "passed", "matched", "groups",
            "largest_group", "pages_visited", "malformed")}


def file_lines(path: str) -> tuple[list[bytes], int]:
    """``(lines, bytes)`` of a file: split at LF, a last line without one
    counted, a CR before the LF no part of the line."""
    with open(path, "rb") as f:
        data = f.read()
    lines = data.split(b"\n")
    if lines and lines[-1] == b"":
        lines.pop()
    return [ln[:-1] if ln.endswith(b"\r") else ln for ln in lines], len(data)


def render(rows) -> bytes:
    return b"".join(
        ip + f"\t{avg:.8e}\t{total:.8e}\n".encode() for ip, avg, total in rows)


def oracle(rankings_path: str, visits_path: str, date_from: str, date_to: str) -> Oracle:
    first = datetime.date.fromisoformat(date_from)
    last = datetime.date.fromisoformat(date_to)
    rankings, r_bytes = file_lines(rankings_path)
    visits, v_bytes = file_lines(visits_path)
    malformed = 0
    rank_of: dict[bytes, int] = {}
    for line in rankings:
        if not line:
            continue
        fields = line.split(b",")
        if len(fields) < 2 or not _RANK.fullmatch(fields[1]):
            malformed += 1
            continue
        rank_of[fields[0]] = int(fields[1])
    passed = matched = 0
    visited = set()
    groups: dict[bytes, list] = {}
    for line in visits:
        if not line:
            continue
        fields = line.split(b",")
        day = _DATE.fullmatch(fields[2]) if len(fields) >= 4 else None
        if not day or not _REVENUE.fullmatch(fields[3]):
            malformed += 1
            continue
        try:
            date = datetime.date(int(day[1]), int(day[2]), int(day[3]))
        except ValueError:
            malformed += 1
            continue
        if not first <= date <= last:
            continue
        passed += 1
        rank = rank_of.get(fields[1])
        if rank is None:
            continue
        matched += 1
        visited.add(fields[1])
        group = groups.setdefault(fields[0], [0.0, 0.0, 0])
        group[0] += float(fields[3])
        group[1] += rank
        group[2] += 1
    rows = sorted(
        ((ip, ranks / n, total) for ip, (total, ranks, n) in groups.items()),
        key=lambda row: (-row[2], row[0]))
    return Oracle(
        rows=rows, table=render(rows), pages=len(rankings), visits=len(visits),
        bytes=r_bytes + v_bytes, passed=passed, matched=matched, groups=len(rows),
        largest_group=max((g[2] for g in groups.values()), default=0),
        pages_visited=len(visited), malformed=malformed)


def parse(table: bytes) -> list:
    """A printed table back into ``[(sourceIP, avgPageRank, totalRevenue)]``;
    raises ``ValueError`` where a line is not ``ip<TAB>number<TAB>number``."""
    if table and not table.endswith(b"\n"):
        raise ValueError("the table's last line has no end")
    rows = []
    for line in table.split(b"\n")[:-1]:
        ip, avg, total = line.split(b"\t")
        rows.append((ip, float(avg), float(total)))
    return rows


def compare(table: bytes, want: Oracle, tolerance: dict):
    """``(verdict, worst relative error)`` of a printed table against the
    oracle's rows: the verdict None if it holds.  The sourceIPs equal as
    SETS; each sourceIP's two numbers within ``tolerance["relative"]`` of
    the oracle's; the printed order non-increasing in the printed total.
    (Not line for line: two totals the tolerance apart may stand either way
    round, and the order is held by the program's own numbers.)"""
    try:
        got = parse(table)
    except ValueError as err:
        return f"the table does not parse: {err}", None
    mine = {ip: (avg, total) for ip, avg, total in want.rows}
    theirs = {ip: (avg, total) for ip, avg, total in got}
    if len(theirs) != len(got):
        return f"a sourceIP is printed twice ({len(got)} lines, {len(theirs)} sourceIPs)", None
    if theirs.keys() != mine.keys():
        missing, extra = mine.keys() - theirs.keys(), theirs.keys() - mine.keys()
        return (f"the sourceIPs differ from the oracle's: {len(missing)} missing "
                f"(e.g. {sorted(missing)[:2]}), {len(extra)} not the oracle's "
                f"(e.g. {sorted(extra)[:2]}); printed {len(theirs)}, the oracle has "
                f"{len(mine)}"), None
    worst, at = 0.0, None
    for ip, pair in theirs.items():
        for x, y in zip(pair, mine[ip]):
            off = abs(x - y) / abs(y) if y else abs(x)
            if off > worst:
                worst, at = off, ip
    if worst > tolerance["relative"]:
        return (f"the numbers differ from the oracle's: {at!r} printed "
                f"{theirs[at][0]:.8e} {theirs[at][1]:.8e}, the oracle has "
                f"{mine[at][0]:.8e} {mine[at][1]:.8e}, relative error {worst:.3e} > "
                f"{tolerance['relative']:.1e}"), worst
    totals = [total for _, _, total in got]
    for i in range(1, len(totals)):
        if totals[i] > totals[i - 1]:
            return (f"the printed order is not by the total, descending: line {i + 1} "
                    f"({totals[i]:.8e}) stands after {totals[i - 1]:.8e}"), worst
    return None, worst
