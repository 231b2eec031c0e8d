"""Join of two delimited tables on the device: HiBench's ``sql/join``.

Pavlo et al.'s Join Task (SIGMOD 2009, section 4.3.4), as HiBench runs it:

    SELECT sourceIP, avg(pageRank), sum(adRevenue) AS totalRevenue
    FROM rankings R JOIN
         (SELECT sourceIP, destURL, adRevenue FROM uservisits UV
          WHERE UV.visitDate >= FROM AND UV.visitDate <= TO) NUV
      ON (R.pageURL = NUV.destURL)
    GROUP BY sourceIP ORDER BY totalRevenue DESC

The first job of the package with two inputs, with fields apart by one
delimiter, with keys of a hundred bytes, and with two shuffles in a row on
different keys: the URL joins, the sourceIP — a VALUE of the join's output
— regroups.  One device (``join_tables``), static shapes throughout:

  1. Map, a block of ``[block_lines, line_width]`` byte rows at a time, ON
     THE DEVICE (``core.bytes_ops``: field boundaries by a delimiter scan,
     fields aligned by a barrel shifter, the date and the two numbers by
     static weights — no gather, no column read).  A Rankings block becomes
     (URL lanes, rank, valid) rows of a page table sized from the file's
     line count.  A UserVisits block is split, its rows whose ``visitDate``
     lies in the window kept, (URL lanes, sourceIP lanes, adRevenue) of
     those moved to the block's head and written into a visit store
     resident on the device at its fill.  The store grows by the tables'
     one rule (``core.kv.rows_to_hold``) ahead of every group of blocks.
  2. Probe, ONCE (``join_probe``): pages and passed visits in one array,
     GROUPED by hash64(URL) — one three-operand sort, the row index its
     last key, so a page stands before the visits of its run — and the key
     rows gathered by it.  A visit's page is the nearest page before it in
     its hash run WHOSE URL LANES EQUAL ITS OWN: where they differ (a
     64-bit collision put two URLs in one run) the visit walks to the page
     before, a ``while_loop`` that runs once in a sound job.  No hash
     decides what joins: it decides which rows meet before the full-key
     compare.  The page's rank goes back to the visit's store row.
  3. Regroup: the matched visits ordered by their sourceIP's bytes (sixteen
     of them, four lanes: ``apps.inverted_index._order_rows``, exact with
     no hash), group ends by a boundary mask, and the three aggregates as
     EXACT integers: adRevenue in millionths (a 64-bit count in two
     words), the ranks and the count, each summed a byte-limb at a time by
     a wrapping prefix sum (exact for a group of up to 2^24 rows) and put
     together with carries.  No float is added on the device.
  4. Order: the groups by the total, descending, ties in sourceIP byte
     order (one stable sort: the groups already stand in that order).

What comes back is arrays (``Joined``); the CLI prints from them.
``locust_tpu/join_reference.py`` is the plain reference.
"""

from __future__ import annotations

import dataclasses
import datetime
import itertools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from locust_tpu import obs
from locust_tpu.apps.inverted_index import _front, _order_rows
from locust_tpu.config import EngineConfig
from locust_tpu.core import bytes_ops, packing
from locust_tpu.core.kv import rows_to_hold

DELIMITER = ord(",")   # HiBench's Hive tables: FIELDS TERMINATED BY ','
IP_BYTES = 16          # UserVisits.sourceIP VARCHAR(16)
IP_LANES = IP_BYTES // 4
INT_DIGITS = 9         # of pageRank, and of adRevenue before its point
FRAC_DIGITS = 6        # of adRevenue after it: the sums' unit is 1e-6
# A UserVisits row from its date on: the date (YYYY-MM-DD), a delimiter, and a revenue
# of INT_DIGITS + a point + FRAC_DIGITS, in a slice this wide.
TAIL_BYTES = 32
# Blocks launched between two reads of the visit store's fill.  The store
# must hold a whole block's rows past its fill before the block is launched
# (every row of a block may pass), so it is grown AHEAD of a group.
GROUP_BLOCKS = 16
# Rows the visit store starts at (core.kv.rows_to_hold's ladder from here),
# and rows of the result the cut brings down.
VISIT_ROWS = 1 << 12
GROUP_ROWS = 1 << 12
# A byte-limb's wrapping prefix sum is exact while a group's rows number
# under 2^32 / 255: no store may pass this.
MAX_ROWS = 1 << 24


@dataclasses.dataclass(frozen=True)
class _JoinPrograms:
    """The join's jitted programs (``_build_join_programs``): one record a
    configuration a process, like the engine's (``engine._programs_for``)."""

    map_pages: Callable   # (page table, totals, lines, at) -> (table, totals)  [table donated]
    map_visits: Callable  # (visit store, totals, lines, window) -> (store, totals)  [store donated]
    grow: Callable        # (store, rows) -> store with empty rows appended
    probe: Callable       # (page table, store, fill) -> (groups table, stats)
    cut: Callable         # (groups table, rows) -> its first rows


def _add64(a_hi, a_lo, b_hi, b_lo):
    """(a + b) of two 64-bit counts held as uint32 (hi, lo), and whether it
    passed 64 bits."""
    lo = a_lo + b_lo
    carry = (lo < a_lo).astype(jnp.uint32)
    hi = a_hi + b_hi
    over = hi < a_hi
    hi2 = hi + carry
    return hi2, lo, over | (hi2 < hi)


def _from_limbs(sums: jax.Array):
    """``sum(sums[:, k] << 8k)`` as (hi, lo, passed 64 bits): a count put
    together from the sums of its byte limbs."""
    n = sums.shape[0]
    hi = lo = jnp.zeros(n, jnp.uint32)
    over = jnp.zeros(n, bool)
    for k in range(sums.shape[1]):
        s, bits = sums[:, k], 8 * k
        if bits == 0:
            part_hi, part_lo = jnp.zeros(n, jnp.uint32), s
        elif bits < 32:
            part_hi, part_lo = s >> (32 - bits), s << bits
        else:
            part_hi, part_lo = s << (bits - 32), jnp.zeros(n, jnp.uint32)
            if bits > 32:
                over |= (s >> (64 - bits)) != 0
        hi, lo, o = _add64(hi, lo, part_hi, part_lo)
        over |= o
    return hi, lo, over


def _byte_limbs(word: jax.Array, n: int) -> list[jax.Array]:
    return [(word >> (8 * k)) & jnp.uint32(0xFF) for k in range(n)]


def _millionths(whole: jax.Array, frac: jax.Array):
    """``whole * 10**6 + frac`` (whole < 2^30, frac < 10^6) as uint32
    (hi, lo): the 32 x 32 product by 16-bit halves, 10^6 = 15 * 2^16 + 16960."""
    a, b = (whole >> 16).astype(jnp.uint32), (whole & 0xFFFF).astype(jnp.uint32)
    mid = a * jnp.uint32(16960) + b * jnp.uint32(15)  # < 2^29
    lo = b * jnp.uint32(16960)                        # < 2^31
    hi = a * jnp.uint32(15) + (mid >> 16)
    hi, lo, _ = _add64(hi, lo, jnp.zeros_like(hi), mid << 16)
    hi, lo, _ = _add64(hi, lo, jnp.zeros_like(hi), frac.astype(jnp.uint32))
    return hi, lo


def _build_join_programs(cfg: EngineConfig) -> _JoinPrograms:
    """Define and jit the join of ``cfg`` (its ``block_lines``,
    ``line_width`` and ``key_width``; the module docstring walks the four
    steps)."""
    width, key_w = cfg.line_width, cfg.key_width
    key_lanes = key_w // 4
    block = cfg.block_lines

    def _head(lines: jax.Array, n_bytes: jax.Array, out: int) -> jax.Array:
        """A row's first ``n_bytes`` bytes, NUL-padded to ``out``."""
        head = lines[:, :out]
        if head.shape[1] < out:
            head = jnp.pad(head, ((0, 0), (0, out - head.shape[1])))
        return jnp.where(jnp.arange(out)[None, :] < n_bytes[:, None], head, jnp.uint8(0))

    def split_pages(lines: jax.Array):
        """Rankings rows ``pageURL,pageRank,...`` -> (rows of URL lanes,
        rank and valid flag; [well-formed, malformed, keys cut])."""
        ends, n_found, length = bytes_ops.field_ends(lines, DELIMITER, 2)
        c0, c1 = ends[:, 0], ends[:, 1]
        is_line = length > 0
        # A row filled to its last byte whose second field no delimiter
        # ends may go on past the row: not a number to trust.
        ended = (n_found == 2) & ~((length == width) & (c1 == width))
        rank_field = bytes_ops.shift_left(
            jnp.pad(lines, ((0, 0), (INT_DIGITS, 0))), c1, INT_DIGITS)
        rank, rank_ok = bytes_ops.parse_uint_right(rank_field, c1 - c0 - 1)
        well = is_line & ended & rank_ok
        url = packing.pack_keys(_head(lines, c0, key_w))
        rows = jnp.concatenate(
            [url, rank.astype(jnp.uint32)[:, None], well.astype(jnp.uint32)[:, None]],
            axis=1)
        counts = jnp.stack([
            jnp.sum(well.astype(jnp.int32)),
            jnp.sum((is_line & ~well).astype(jnp.int32)),
            jnp.sum((well & (c0 > key_w)).astype(jnp.int32)),
        ])
        return jnp.where(well[:, None], rows, jnp.uint32(0)), counts

    def join_map_pages(table: jax.Array, totals: jax.Array, lines: jax.Array,
                       at: jax.Array):
        rows, counts = split_pages(lines)
        return (jax.lax.dynamic_update_slice(table, rows, (at, jnp.int32(0))),
                totals + counts)

    def split_visits(lines: jax.Array, window: jax.Array):
        """UserVisits rows ``sourceIP,destURL,visitDate,adRevenue,...`` ->
        (rows of URL lanes, sourceIP lanes and the revenue's two words;
        which of them lie in the window; [malformed, keys cut])."""
        ends, n_found, length = bytes_ops.field_ends(lines, DELIMITER, 4)
        c0, c1, c2, c3 = (ends[:, k] for k in range(4))
        is_line = length > 0
        ended = (n_found == 4) & ~((length == width) & (c3 == width))
        url_len = c1 - c0 - 1
        url = bytes_ops.shift_left(lines, c0 + 1, key_w)
        url = jnp.where(jnp.arange(key_w)[None, :] < url_len[:, None], url, jnp.uint8(0))
        tail = bytes_ops.shift_left(lines, c1 + 1, TAIL_BYTES)
        date_len = c2 - c1 - 1
        ymd, date_ok = bytes_ops.parse_date(tail, date_len)
        # The revenue in the tail: [r0, r1), its point (or its end) at p.
        r0, r1 = date_len + 1, c3 - c1 - 1
        col = jnp.arange(TAIL_BYTES, dtype=jnp.int32)[None, :]
        is_point = (tail == ord(".")) & (col >= r0[:, None]) & (col < r1[:, None])
        p = jnp.min(jnp.where(is_point, col, r1[:, None]), axis=-1)
        whole, whole_ok = bytes_ops.parse_uint_right(
            bytes_ops.shift_left(
                jnp.pad(tail, ((0, 0), (INT_DIGITS, 0))), p, INT_DIGITS),
            p - r0)
        n_frac = jnp.maximum(r1 - p - 1, 0)
        frac, frac_ok = bytes_ops.parse_fraction_left(
            bytes_ops.shift_left(tail, p + 1, FRAC_DIGITS), n_frac)
        revenue_ok = (whole_ok & frac_ok & (r1 <= TAIL_BYTES)
                      & ((p == r1) | (n_frac >= 1)))  # "12." is no number
        well = is_line & ended & date_ok & revenue_ok
        passed = well & (ymd >= window[0]) & (ymd <= window[1])
        hi, lo = _millionths(whole, frac)
        rows = jnp.concatenate(
            [packing.pack_keys(url), packing.pack_keys(_head(lines, c0, IP_BYTES)),
             hi[:, None], lo[:, None]], axis=1)
        counts = jnp.stack([
            jnp.sum((is_line & ~well).astype(jnp.int32)),
            jnp.sum((passed & ((url_len > key_w) | (c0 > IP_BYTES))).astype(jnp.int32)),
        ])
        return rows, passed, counts

    def join_map_visits(store: jax.Array, totals: jax.Array, lines: jax.Array,
                        window: jax.Array):
        rows, passed, counts = split_visits(lines, window)
        # The passed rows to the block's head, in line order; what follows
        # them in the store is overwritten by the next block or lies past
        # the fill.
        front = _front(passed)
        front = jnp.where(front >= block, front - block, front)
        n_passed = jnp.sum(passed.astype(jnp.int32))
        store = jax.lax.dynamic_update_slice(
            store, rows[front], (totals[0], jnp.int32(0)))
        return store, totals + jnp.concatenate([n_passed[None], counts])

    def join_grow(store: jax.Array, rows: int) -> jax.Array:
        # A function of its own for its name in a trace: jit_join_grow.
        return jnp.pad(store, ((0, rows - store.shape[0]), (0, 0)))

    def join_probe(table: jax.Array, store: jax.Array, fill: jax.Array):
        n_pages, cap = table.shape[0], store.shape[0]
        n = n_pages + cap
        url = jnp.concatenate([table[:, :key_lanes], store[:, :key_lanes]])
        store_row = jnp.arange(cap, dtype=jnp.int32)
        live = jnp.concatenate([table[:, key_lanes + 1] != 0, store_row < fill])
        h1, h2 = packing.hash_pair(url)
        # The row index is the last KEY: a run's pages stand before its
        # visits, in file order, and a dead row (its top bit) after both.
        tagged = jnp.arange(n, dtype=jnp.uint32) | jnp.where(
            live, jnp.uint32(0), jnp.uint32(1 << 31))
        s_h1, s_h2, tagged = jax.lax.sort((h1, h2, tagged), num_keys=3, is_stable=False)
        perm = (tagged & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
        s_live = tagged < jnp.uint32(1 << 31)
        s_page, s_visit = s_live & (perm < n_pages), s_live & (perm >= n_pages)
        s_url = url[perm]
        pos = jnp.arange(n, dtype=jnp.int32)
        run_new = (pos == 0) | (s_h1 != jnp.roll(s_h1, 1)) | (s_h2 != jnp.roll(s_h2, 1))
        run_first = jax.lax.cummax(jnp.where(run_new, pos, 0))
        last_page = jax.lax.cummax(jnp.where(s_page, pos, -1))
        page_before = jnp.pad(last_page, (1, 0), constant_values=-1)[:-1]

        def looks(cand):
            """(the candidate page stands in the row's hash run, its URL is
            the row's)."""
            in_run = cand >= run_first
            return in_run, jnp.all(s_url[jnp.maximum(cand, 0)] == s_url, axis=-1)

        def walk(state):
            cand, in_run, equal = state
            # Of a URL listed twice the LAST row stands, as a dict's would:
            # it is the nearest, and found first.
            cand = jnp.where(s_visit & in_run & ~equal,
                             page_before[jnp.maximum(cand, 0)], cand)
            return (cand, *looks(cand))

        cand, in_run, equal = jax.lax.while_loop(
            lambda st: jnp.any(s_visit & st[1] & ~st[2]), walk,
            (last_page, *looks(last_page)))
        matched = s_visit & in_run & equal
        at_page = jnp.where(matched, cand, n)
        s_rank = jnp.concatenate(
            [table[:, key_lanes], jnp.zeros(cap, jnp.uint32)])[perm]
        rank = s_rank[jnp.minimum(at_page, n - 1)]
        pages_visited = jnp.sum(
            jnp.zeros(n, jnp.int32).at[at_page].set(1, mode="drop"))
        # The page's rank back to the visit's store row; NO_PAGE elsewhere.
        no_page = jnp.uint32(0xFFFFFFFF)
        rank_at = jnp.full(cap, no_page).at[
            jnp.where(matched, perm - n_pages, cap)].set(rank, mode="drop")
        keep = rank_at != no_page
        n_matched = jnp.sum(keep.astype(jnp.int32))

        # The second shuffle: its key, the sourceIP, is a value of the first.
        ip = store[:, key_lanes:key_lanes + IP_LANES]
        order = _order_rows(
            [(~keep).astype(jnp.uint32)] + [ip[:, j] for j in range(IP_LANES)])
        hi, lo = store[:, key_lanes + IP_LANES], store[:, key_lanes + IP_LANES + 1]
        limbs = jnp.stack(
            _byte_limbs(lo, 4) + _byte_limbs(hi, 3) + _byte_limbs(rank_at, 4), axis=1)
        g = jnp.concatenate([ip, limbs], axis=1)[order]
        g_live = store_row < n_matched  # the dead rows stand last
        g_ip = g[:, :IP_LANES]
        starts = g_live & ((store_row == 0) | jnp.any(g_ip != jnp.roll(g_ip, 1, axis=0), axis=-1))
        ends = g_live & (jnp.roll(starts, -1) | (store_row == n_matched - 1))
        n_groups = jnp.sum(starts.astype(jnp.int32))
        running = jnp.cumsum(
            jnp.where(g_live[:, None], g[:, IP_LANES:], jnp.uint32(0)),
            axis=0, dtype=jnp.uint32)
        end_rows = _front(ends)
        is_group = end_rows < cap
        end_rows = jnp.where(is_group, end_rows, end_rows - cap)
        upto = running[end_rows]
        sums = upto - jnp.pad(upto, ((1, 0), (0, 0)))[:-1]  # wraps exactly
        count = end_rows - jnp.pad(end_rows, (1, 0), constant_values=-1)[:-1]
        t_hi, t_lo, t_over = _from_limbs(sums[:, :7])
        r_hi, r_lo, r_over = _from_limbs(sums[:, 7:])
        overflow = jnp.any(is_group & (t_over | r_over))
        groups = jnp.concatenate(
            [g_ip[end_rows],
             jnp.stack([t_hi, t_lo, r_hi, r_lo, count.astype(jnp.uint32)], axis=1)],
            axis=1)
        # By the total, descending; ties stay in sourceIP byte order.
        by_total = _order_rows(
            [(~is_group).astype(jnp.uint32), jnp.zeros(cap, jnp.uint32), ~t_hi, ~t_lo])
        stats = jnp.stack([n_groups, n_matched, pages_visited,
                           overflow.astype(jnp.int32)])
        return groups[by_total], stats

    def join_cut(groups: jax.Array, rows: int) -> jax.Array:
        return groups[:rows]

    return _JoinPrograms(
        map_pages=jax.jit(join_map_pages, donate_argnums=0),
        map_visits=jax.jit(join_map_visits, donate_argnums=0),
        grow=jax.jit(join_grow, static_argnames="rows"),
        probe=jax.jit(join_probe),
        cut=jax.jit(join_cut, static_argnames="rows"),
    )


@dataclasses.dataclass
class Joined:
    """The query's table as arrays, a row a sourceIP, ordered by the total
    descending (ties by the sourceIP's bytes), and what the job counted.

    Attributes:
      ips: uint8 ``[groups, 16]``, NUL-padded.
      revenue_millionths: uint64 ``[groups]`` — ``sum(adRevenue)``, exact.
      rank_sums: uint64 ``[groups]``; counts: int64 ``[groups]`` —
        ``avg(pageRank)`` is their quotient.
      pages, visits: lines of the two tables.  passed: well-formed
        visits inside the window.  matched: those of them whose URL is a page's.
        pages_visited: distinct pages they matched.
      malformed: rows (of either table) whose fields do not parse; they
        take no part.  cut_keys: URLs past ``key_width`` (sourceIPs past
        16 bytes), joined by their head.
      grows: growth steps the visit store took; store_rows its capacity at
        the end.
    """

    ips: np.ndarray
    revenue_millionths: np.ndarray
    rank_sums: np.ndarray
    counts: np.ndarray
    pages: int = 0
    visits: int = 0
    passed: int = 0
    matched: int = 0
    pages_visited: int = 0
    malformed: int = 0
    cut_keys: int = 0
    grows: int = 0
    store_rows: int = 0

    def __len__(self) -> int:
        return self.ips.shape[0]

    @property
    def totals(self) -> np.ndarray:
        """``totalRevenue``, float64: the exact sum over its unit."""
        return self.revenue_millionths.astype(np.float64) / 1e6

    @property
    def averages(self) -> np.ndarray:
        """``avg(pageRank)``, float64."""
        return self.rank_sums.astype(np.float64) / np.maximum(self.counts, 1)


def date_number(day: str) -> int:
    """``YYYY-MM-DD`` as the integer the device compares dates by."""
    d = datetime.date.fromisoformat(day)
    return d.year * 10000 + d.month * 100 + d.day


def _blocks(rows, block: int, width: int):
    """``rows`` — an array of lines, or an iterable of arrays of at most
    ``block`` lines each (``io.loader.StreamingCorpus``: a file read as the
    job goes, never held whole) — as ``(lines, block)`` pairs, a short
    block padded with empty lines; a source of no line gives one empty
    block."""
    source = rows
    if isinstance(rows, np.ndarray):
        source = (rows[at:at + block] for at in range(0, rows.shape[0], block))
    n = None
    for blk in source:
        n = blk.shape[0]
        if blk.ndim != 2 or blk.shape[1] != width or n > block:
            raise ValueError(
                f"a block of shape {blk.shape}, wanted [<= {block}, {width}]")
        if n < block:
            blk = np.concatenate([blk, np.zeros((block - n, width), np.uint8)])
        yield n, blk
    if n is None:
        yield 0, np.zeros((block, width), np.uint8)


def join_tables(
    pages: np.ndarray,
    visits,
    cfg: EngineConfig | None = None,
    date_from: str = "1999-01-01",
    date_to: str = "2000-01-01",
) -> Joined:
    """Host API: the two tables' padded byte rows at ``cfg.line_width`` ->
    the query's table as arrays.  ``pages`` is an array
    (``io.loader.load_rows``: the page table is sized from its line count);
    ``visits`` an array too, or an ITERABLE of its blocks of at most
    ``cfg.block_lines`` rows in order (``io.loader.StreamingCorpus``, read
    ahead of the device by whoever hands it over and never held whole).

    No capacity is the caller's to guess: the visit store starts at what
    holds one group of ``GROUP_BLOCKS`` blocks (``VISIT_ROWS`` at least)
    and grows by the tables' one rule ahead of every later group.  More
    rows than ``MAX_ROWS`` in either (the exact sums' limit) raise; nothing
    is ever cut to fit.
    """
    from locust_tpu.engine import _programs_for

    cfg = cfg or EngineConfig(line_width=256, key_width=128)
    window = jax.device_put(
        np.asarray([date_number(date_from), date_number(date_to)], np.int32))
    programs = _programs_for(("join", cfg), lambda: _build_join_programs(cfg))
    bl, width, key_lanes = cfg.block_lines, cfg.line_width, cfg.key_width // 4

    def too_many(rows: int) -> None:
        if rows > MAX_ROWS:
            raise ValueError(
                f"{rows} rows are more than the {MAX_ROWS} the join's exact "
                "sums hold on one device")

    page_rows = max(1, -(-pages.shape[0] // bl)) * bl
    too_many(page_rows)
    table = jnp.zeros((page_rows, key_lanes + 2), jnp.uint32)
    page_totals = jnp.zeros(3, jnp.int32)
    with obs.span("join.map", table="pages", blocks=page_rows // bl):
        for i, (_, blk) in enumerate(_blocks(pages, bl, width)):
            with obs.span("join.h2d", bytes=blk.nbytes):
                on_device = jax.device_put(blk)
            table, page_totals = programs.map_pages(
                table, page_totals, on_device, np.int32(i * bl))

    # [the store's fill, malformed, keys cut]: on the DEVICE across a group
    # — an int() a block would serialize dispatch.
    totals = jnp.zeros(3, jnp.int32)
    store, cap, n_visits, grows = None, 0, 0, 0
    blocks = _blocks(visits, bl, width)
    while (head := next(blocks, None)) is not None:
        group = itertools.chain([head], itertools.islice(blocks, GROUP_BLOCKS - 1))
        if store is None:
            cap = need = rows_to_hold(VISIT_ROWS, GROUP_BLOCKS * bl)
            store = jnp.zeros((cap, key_lanes + IP_LANES + 2), jnp.uint32)
        else:
            with obs.span("engine.sync", what="join.fill"):
                filled = int(totals[0])
            need = rows_to_hold(cap, filled + GROUP_BLOCKS * bl)
        with obs.span("join.map", table="visits", rows=need) as sp:
            if need != cap:
                store = programs.grow(store, rows=need)
                cap, grows = need, grows + 1
            n_blocks = 0
            for n, blk in group:
                too_many(n_visits := n_visits + n)
                with obs.span("join.h2d", bytes=blk.nbytes):
                    on_device = jax.device_put(blk)
                store, totals = programs.map_visits(store, totals, on_device, window)
                n_blocks += 1
            sp.set(blocks=n_blocks)

    with obs.span("join.probe", pages=page_rows, rows=cap):
        groups, stats = programs.probe(table, store, totals[0])
        with obs.span("engine.sync", what="join.probe"):
            (n_groups, matched, pages_visited, overflow), totals, page_totals = (
                jax.tree.map(int, jax.device_get(
                    (tuple(stats), tuple(totals), tuple(page_totals)))))
    if overflow:
        raise ValueError(
            "a group's sum passed 64 bits of millionths: no total to print")
    rows = min(rows_to_hold(GROUP_ROWS, n_groups), cap)
    with obs.span("join.d2h", bytes=rows * groups.shape[1] * 4):
        out = np.asarray(programs.cut(groups, rows=rows))[:n_groups]
    passed, bad_visits, cut_visits = totals
    _, bad_pages, cut_pages = page_totals
    wide = out[:, IP_LANES:].astype(np.uint64)
    joined = Joined(
        ips=np.ascontiguousarray(out[:, :IP_LANES]).astype(">u4").view(np.uint8)
        .reshape(-1, IP_BYTES),
        revenue_millionths=(wide[:, 0] << np.uint64(32)) | wide[:, 1],
        rank_sums=(wide[:, 2] << np.uint64(32)) | wide[:, 3],
        counts=out[:, IP_LANES + 4].astype(np.int64),
        pages=pages.shape[0], visits=n_visits, passed=passed,
        matched=matched, pages_visited=pages_visited,
        malformed=bad_pages + bad_visits, cut_keys=cut_pages + cut_visits,
        grows=grows, store_rows=cap,
    )
    obs.metric_inc("join.pages", joined.pages)
    obs.metric_inc("join.visits", joined.visits)
    obs.metric_inc("join.passed", passed)
    obs.metric_inc("join.matched", matched)
    obs.metric_inc("join.groups", n_groups)
    obs.metric_inc("join.key_overflow", joined.cut_keys)
    obs.metric_inc("join.malformed", joined.malformed)
    obs.metric_inc("join.grows", grows)
    return joined
