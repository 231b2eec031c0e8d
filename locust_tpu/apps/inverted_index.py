"""Inverted index: word -> sorted unique doc ids (BASELINE.json configs[4]).

The workload whose reduce COLLECTS: emits are (word, doc_id) and the
reduce is "list the distinct values of a key" — an output of a length
nobody knows before the job and of the order of the input, where every
other fold of the package sums into a slot (SURVEY.md §7.2 M5).

One device (``build_index``), static shapes throughout:

  1. Map: tokenize a block (ops/map_stage), value = the line's doc id.
  2. In-block dedup: one sort by (validity, hash64(key), doc) — 4 key
     operands regardless of key width, payload rows by one index gather
     (``_sort_pairs``) — and a boundary mask on FULL key + doc; the
     survivors move to the block's head.
  3. Append: the head is written into a pair store resident on the device
     at its fill.  The store grows by the tables' one rule
     (core/kv.rows_to_hold) ahead of every group of blocks; nothing is
     re-sorted a block (a collect has no combiner that keeps state small:
     the fold this replaced re-sorted the whole pair set every block and
     held 163,840 pairs).
  4. Collect, once: the store GROUPED by (dead, hash64(key)) — one
     five-operand sort that carries the doc id and the row index, and
     one gather of whole key rows — so equal keys stand together; a row
     whose full key differs from its neighbour's starts a word ENTRY.
     The entries (a sixteenth as many rows as pairs, at 64 lines a
     document) are ordered by their bytes (``_order_rows``) and ranked,
     entries of equal bytes — the pieces of a word that a 64-bit
     collision interleaved with another — alike; the rank goes back to
     the rows and one two-key sort by (rank, doc) puts words in byte
     order and a word's docs ascending, equal neighbours being the
     duplicates of a document that spans blocks.  No hash decides what is
     listed: it decides which rows meet before the full-key compare.
     Postings and word starts are compacted by two narrow sorts; the
     result is CSR (``Postings``: words, offsets, postings), the dict
     spelling built from it for those who ask.

The mesh variant (``DistributedInvertedIndex``) still carries a fixed,
per-shard pair table that it dedups every round.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from locust_tpu import obs
from locust_tpu.config import EngineConfig
from locust_tpu.core import bytes_ops, packing
from locust_tpu.core.kv import KVBatch, grow_table, rows_to_hold
from locust_tpu.ops.map_stage import tokenize_block

logger = logging.getLogger("locust_tpu")


def _sort_pairs(batch: KVBatch) -> KVBatch:
    """Group by (validity, hash64(key)) with values as a tie-break sort key.

    4 sort operands + an index payload regardless of key width — the hash
    trick from ops/process_stage._hash_sort, extended with the value as the
    least-significant key so each word's doc ids come out ascending.
    """
    lanes, values, valid = batch.key_lanes, batch.values, batch.valid
    n = lanes.shape[0]
    invalid = (~valid).astype(jnp.uint32)
    h1, h2 = packing.hash_pair(lanes)
    idx = jnp.arange(n, dtype=jnp.int32)
    out = jax.lax.sort((invalid, h1, h2, values, idx), num_keys=4)
    sidx = out[4]
    return KVBatch(
        key_lanes=lanes[sidx], values=values[sidx], valid=valid[sidx]
    )


def _dedup_sorted_pairs(s: KVBatch) -> tuple[KVBatch, jax.Array]:
    """Mark the first of each identical (word, doc) run; return the
    re-compacted batch and the surviving-pair count."""
    keep = s.valid & _pair_starts(s.key_lanes, s.values)
    deduped = KVBatch(key_lanes=s.key_lanes, values=s.values, valid=keep)
    d = _sort_pairs(deduped)  # compact survivors to the prefix, still ordered
    return d, jnp.sum(keep.astype(jnp.int32))


def _word_starts(lanes: jax.Array) -> jax.Array:
    """Rows whose key differs from the row before (row 0 starts one)."""
    first = jnp.arange(lanes.shape[0]) == 0
    return first | jnp.any(lanes != jnp.roll(lanes, 1, axis=0), axis=-1)


def _pair_starts(lanes: jax.Array, values: jax.Array) -> jax.Array:
    """Rows whose (key, value) differs from the row before."""
    return _word_starts(lanes) | (values != jnp.roll(values, 1))


def default_pairs_capacity(cfg: EngineConfig, mult: int = 2) -> int:
    """Default distinct-(word, doc) pair capacity: ``mult`` rounds of
    emits with a 4096 floor.  The pair table is CORPUS-level state, not
    per-block — a small block size must not shrink it (r4 apps battery:
    tiny-block configs raised on ordinary vocabularies; the floor costs
    ~150KB).  The sizing rule of the distributed index (``mult=4``: pairs
    accumulate across rounds) and the tf counter, which hold a FIXED
    table and raise past it; for the single-device index it is only the
    capacity its pair store STARTS at (``build_index``: the store grows)."""
    return max(mult * cfg.emits_per_block, 4096)


# Blocks appended to the pair store between two reads of its fill.  The
# store must hold a whole block's emits past its fill before the block is
# launched (a block's survivors are written as one slice), so it is grown
# AHEAD of a group by what the group can emit at most; the host reads the
# fill once a group, never once a block.
COLLECT_GROUP_BLOCKS = 16
# The word rows the cut program brings down start at this many and grow
# by core.kv's rule with the distinct words the collect counted.
WORD_ROWS = 1 << 16


def _front(marked: jax.Array) -> jax.Array:
    """The indices of the ``marked`` rows in ascending order at the front,
    then the unmarked rows' indices plus the length: one sort of ONE
    operand (its keys are distinct, so it need not be stable — a stable
    sort pays the chip another operand)."""
    n = marked.shape[0]
    row = jnp.arange(n, dtype=jnp.int32)
    return jax.lax.sort(jnp.where(marked, row, row + n), is_stable=False)


# Key columns a pass of ``_order_rows`` sorts by.
RADIX_KEYS = 2


def _order_rows(keys: list[jax.Array]) -> jax.Array:
    """The permutation that orders rows by ``keys`` (uint32 ``[n]`` columns,
    most significant first), ties in input order: a least-significant-
    digit radix sort whose digit is ``RADIX_KEYS`` whole columns — ONE
    stable three-operand ``lax.sort`` (two columns gathered by the running
    permutation, and the permutation) in a loop over the column pairs,
    last pair first.  Why not one sort with every column a key: the chip's
    compiler takes time with the SQUARE of a sort's operands (6 s for one,
    88 s for five, 295 s for ten on the sandbox's CPU for a described v5e,
    and a ten-column sort did not compile in fourteen minutes; PERF.md
    section 6, PR 45), and a program every job needs must not take a
    quarter of an hour to get.  A pass pays a one-word gather a column
    (8.6 ns a row on a v5e, as much as a whole key row), so this orders
    the collect's word ENTRIES — a million rows at the benchmark's size —
    and never the pair store (PERF.md section 6, PR 47)."""
    n = keys[0].shape[0]
    if len(keys) % RADIX_KEYS:  # a constant column orders nothing
        keys = [jnp.zeros(n, jnp.uint32)] * (-len(keys) % RADIX_KEYS) + keys
    digits = jnp.stack(keys).reshape(-1, RADIX_KEYS, n)

    def a_pass(i, perm):
        digit = jax.lax.dynamic_index_in_dim(
            digits, digits.shape[0] - 1 - i, keepdims=False)
        return jax.lax.sort(
            (*(column[perm] for column in digit), perm),
            num_keys=RADIX_KEYS, is_stable=True,
        )[-1]

    return jax.lax.fori_loop(
        0, digits.shape[0], a_pass, jnp.arange(n, dtype=jnp.int32))


@dataclasses.dataclass(frozen=True)
class _IndexPrograms:
    """The single-device index's jitted programs (``_build_index_programs``):
    one record a configuration a process, like the engine's
    (``engine._programs_for``)."""

    block: Callable    # (lines, doc ids) -> (block's distinct pairs at its head, counts [its pairs, tokens dropped, keys cut])
    append: Callable   # (store, totals [fill, dropped, cut], a block's pairs, its counts) -> (store, totals)  [store donated]
    grow: Callable     # (store, rows) -> store with empty rows appended
    collect: Callable  # (store, fill) -> (key lanes and doc ids grouped by hash, the word entries' first rows, n_live, n_entries)
    cut: Callable      # (grouped key lanes, doc ids, entries' first rows, n_live, rows) -> (word key bytes, offsets, postings, n_pairs, n_words)


def _build_index_programs(cfg: EngineConfig) -> _IndexPrograms:
    """Define and jit the collect of ``cfg``: a reduce that LISTS, where
    every other fold of the package sums.

    ``block``: one block tokenised and deduplicated within itself — the
    one block-sized hash sort there always was (``_sort_pairs``), the
    boundary mask on full keys, and the survivors moved to the block's
    head by a one-operand sort of their row indices.  ``append``: that
    head written into the resident pair store at its fill.  ``collect``,
    ONCE a job: the whole store GROUPED by (dead, hash64(key)) — the one
    store-sized sort with more than two operands, five, the doc id and the
    row index its payload — and the key rows gathered by it, ONE gather of
    eight lanes a row; a live row whose full key differs from the row
    before starts a word ENTRY, and the entries' first rows move to the
    front.  An entry is a word, or a piece of one where a 64-bit collision
    interleaved two words in one hash run.  ``cut``, for as many rows as
    the job has entries (a capacity of ``core.kv.rows_to_hold``'s ladder):
    the entries ordered by their key lanes (``_order_rows``: the radix
    sort, four passes over the ENTRIES) and given their dense rank, equal
    bytes one rank — which folds a collision's pieces back, nothing after
    it looks at a hash; the rank carried to every row of its entry (the
    ranks' differences scattered to the entries' first rows and summed
    along the store); one two-key sort by (rank, doc id), so words stand in
    byte order, a word's doc ids ascending, and neighbours equal in both
    are the duplicates a document spread over two blocks leaves; then the
    distinct pairs' doc ids (the postings), the words' posting offsets and
    their key bytes, each moved to the front by a narrow sort."""
    n_lanes = cfg.key_lanes
    key_w, emits = cfg.key_width, cfg.emits_per_line

    def index_block(lines: jax.Array, doc_ids: jax.Array):
        res = tokenize_block(lines, cfg)
        values = jnp.repeat(doc_ids.astype(jnp.int32), emits)
        batch = KVBatch.from_bytes(
            res.keys.reshape(-1, key_w), values, res.valid.reshape(-1)
        )
        s = _sort_pairs(batch)
        keep = s.valid & _pair_starts(s.key_lanes, s.values)
        n = s.size
        row = jnp.arange(n, dtype=jnp.int32)
        front = jnp.sort(jnp.where(keep, row, row + n))
        front = jnp.where(front >= n, front - n, front)
        n_kept = jnp.sum(keep.astype(jnp.int32))
        head = KVBatch(
            key_lanes=s.key_lanes[front], values=s.values[front],
            valid=row < n_kept,
        )
        return head, jnp.stack([n_kept, res.overflow, _keys_cut(lines)])

    def _keys_cut(lines: jax.Array) -> jax.Array:
        """Emitted tokens longer than ``key_width``: a token start whose
        next ``key_width`` bytes are all inside the token."""
        in_token = ~bytes_ops.delimiter_mask(lines)
        starts = bytes_ops.token_starts(in_token)
        inside = jnp.pad(in_token, ((0, 0), (1, key_w))).astype(jnp.int32)
        upto = jnp.cumsum(inside, axis=-1)  # upto[:, j] = in-token bytes before byte j
        width = lines.shape[-1]
        run = upto[:, key_w + 1 : key_w + 1 + width] - upto[:, :width]
        emitted = bytes_ops.token_ids(starts) < emits
        return jnp.sum((starts & emitted & (run == key_w + 1)).astype(jnp.int32))

    def index_append(store: KVBatch, totals: jax.Array, head: KVBatch,
                     counts: jax.Array):
        at = (totals[0], jnp.int32(0))
        return KVBatch(
            key_lanes=jax.lax.dynamic_update_slice(
                store.key_lanes, head.key_lanes, at),
            values=jax.lax.dynamic_update_slice(
                store.values, head.values, at[:1]),
            valid=jax.lax.dynamic_update_slice(
                store.valid, head.valid, at[:1]),
        ), totals + counts

    def index_grow(store: KVBatch, rows: int) -> KVBatch:
        # A function of its own for its name in a trace: jit_index_grow.
        return grow_table(store, rows)

    def index_collect(store: KVBatch, fill: jax.Array):
        n = store.size
        row = jnp.arange(n, dtype=jnp.int32)
        # Past the fill lies the last block's tail and rows never written.
        live = store.valid & (row < fill)
        h1, h2 = packing.hash_pair(store.key_lanes)
        # The doc id rides the sort as payload: nothing one word wide is
        # gathered over the store.
        _, _, _, docs, perm = jax.lax.sort(
            ((~live).astype(jnp.uint32), h1, h2, store.values, row),
            num_keys=3, is_stable=False,
        )
        s_lanes = store.key_lanes[perm]
        n_live = jnp.sum(live.astype(jnp.int32))  # dead rows sort last
        starts = (row < n_live) & _word_starts(s_lanes)
        entry_rows = _front(starts)
        return (s_lanes, docs, entry_rows, n_live,
                jnp.sum(starts.astype(jnp.int32)))

    def index_cut(s_lanes: jax.Array, docs: jax.Array, entry_rows: jax.Array,
                  n_live: jax.Array, rows: int):
        n = s_lanes.shape[0]
        row = jnp.arange(n, dtype=jnp.int32)
        slot = jnp.arange(rows, dtype=jnp.int32)
        # The entries, ordered by their bytes.  A slot past the last entry
        # takes the largest key: ties stand in input order, so the entries
        # fill the ordered slots' head even beside a word of 0xFF bytes.
        at = entry_rows[:rows]  # an entry's first row; n or more past the last
        is_entry = at < n
        n_entries = jnp.sum(is_entry.astype(jnp.int32))
        e_lanes = jnp.where(
            is_entry[:, None], s_lanes[jnp.where(is_entry, at, at - n)],
            jnp.uint32(0xFFFFFFFF))
        order = _order_rows([e_lanes[:, j] for j in range(n_lanes)])
        o_lanes = e_lanes[order]
        # Entries of equal bytes — the pieces a hash collision cut a word
        # into — take one rank: the word's place in byte order.
        word_new = (slot < n_entries) & _word_starts(o_lanes)
        _, rank = jax.lax.sort(
            (order, jnp.cumsum(word_new.astype(jnp.int32)) - 1),
            num_keys=1, is_stable=False)
        # The rank to every row of its entry: the ranks' differences at
        # the entries' first rows, summed along the store (int32 wraps
        # exactly) — a million scattered words, where a gather by the
        # rows' entry numbers would be one word a store row.
        steps = rank - jnp.pad(rank, (1, 0))[:-1]
        row_rank = jnp.cumsum(
            jnp.zeros(n, jnp.int32).at[at].add(steps, mode="drop"))
        live = row < n_live  # dead rows stand last, before this sort and after
        row_rank = jnp.where(live, row_rank, jnp.int32(2**31 - 1))
        # Words in byte order, a word's docs ascending; equal neighbours
        # are the duplicates of a document that spans blocks.
        row_rank, docs = jax.lax.sort(
            (row_rank, docs), num_keys=2, is_stable=False)
        first = live & ((row == 0) | (row_rank != jnp.roll(row_rank, 1)))
        keep = live & (first | (docs != jnp.roll(docs, 1)))
        kept = keep.astype(jnp.int32)
        before = jnp.cumsum(kept) - kept  # distinct pairs ahead of a row
        _, postings = jax.lax.sort(
            (jnp.where(keep, row, row + n), docs), num_keys=1, is_stable=False)
        word_rows = _front(first)[:rows]
        offsets = before[jnp.where(word_rows < n, word_rows, word_rows - n)]
        # The distinct words' keys, to the ordered slots' head.
        head = _front(word_new)
        words = o_lanes[jnp.where(head < rows, head, head - rows)]
        return (packing.unpack_keys(words), offsets, postings,
                jnp.sum(kept), jnp.sum(word_new.astype(jnp.int32)))

    return _IndexPrograms(
        block=jax.jit(index_block),
        append=jax.jit(index_append, donate_argnums=0),
        grow=jax.jit(index_grow, static_argnames="rows"),
        collect=jax.jit(index_collect),
        cut=jax.jit(index_cut, static_argnames="rows"),
    )


@dataclasses.dataclass
class Postings:
    """An inverted index as arrays (CSR): word ``w`` is ``words[w]`` (its
    NUL-padded key bytes; the words stand in byte order) and its documents
    are ``postings[offsets[w]:offsets[w + 1]]``, distinct and ascending.

    Attributes:
      words: uint8 ``[n_words, key_width]``.
      offsets: int64 ``[n_words + 1]``.
      postings: int32 ``[n_pairs]``.
      dropped_tokens: tokens past ``emits_per_line`` — their postings are
        MISSING.  ``cut_keys``: emitted tokens longer than ``key_width``,
        indexed under their first ``key_width`` bytes.
      grows: growth steps the pair store took; ``store_rows`` its capacity
        at the end.
    """

    words: np.ndarray
    offsets: np.ndarray
    postings: np.ndarray
    dropped_tokens: int = 0
    cut_keys: int = 0
    grows: int = 0
    store_rows: int = 0

    def __len__(self) -> int:
        return self.words.shape[0]

    def head(self, n_words: int) -> "Postings":
        """The first ``n_words`` words' part of the index."""
        n_words = max(0, min(n_words, len(self)))
        return dataclasses.replace(
            self, words=self.words[:n_words],
            offsets=self.offsets[: n_words + 1],
            postings=self.postings[: int(self.offsets[n_words])],
        )

    def to_dict(self) -> dict[bytes, list[int]]:
        """``{word: sorted distinct doc ids}`` — a ``bytes`` a word and an
        ``int`` a posting, for the callers that merge or look words up
        (library callers, serve's plan results); the CLI prints from the
        arrays (``bytes_ops.render_postings``)."""
        docs = self.postings.tolist()
        bounds = self.offsets.tolist()
        return {
            w: docs[bounds[i] : bounds[i + 1]]
            for i, w in enumerate(bytes_ops.rows_to_strings(self.words))
        }


def build_index(
    lines: list[bytes] | np.ndarray,
    doc_ids: np.ndarray,
    cfg: EngineConfig | None = None,
    pairs_capacity: int | None = None,
) -> Postings:
    """Host API: lines + per-line doc ids -> the inverted index as arrays.

    Streams the corpus through fixed-shape blocks like the WordCount
    engine — no line-count cap — and COLLECTS: each block's distinct
    (word, doc) pairs are appended to a pair store resident on the device,
    which is sized and grown by the tables' one rule
    (``core.kv.rows_to_hold`` / ``grow_table``, up from
    ``default_pairs_capacity``) ahead of every group of
    ``COLLECT_GROUP_BLOCKS`` blocks, so no size one device
    holds is an error and nobody guesses a capacity; the store is ordered
    once, at the end (``_build_index_programs``).  ``pairs_capacity`` is a
    LIMIT a caller may set: more distinct pairs than that raise, as a
    truncated index is silently wrong.
    """
    from locust_tpu.engine import _programs_for

    cfg = cfg or EngineConfig()
    if not isinstance(lines, np.ndarray):
        rows = bytes_ops.strings_to_rows(list(lines), cfg.line_width)
    else:
        rows = lines
    ids = np.asarray(doc_ids, np.int32)
    if rows.shape[0] != ids.shape[0]:
        raise ValueError(f"{rows.shape[0]} lines but {ids.shape[0]} doc ids")
    programs = _programs_for(
        ("index", cfg), lambda: _build_index_programs(cfg)
    )

    bl, per_block = cfg.block_lines, cfg.emits_per_block
    nblocks = max(1, -(-rows.shape[0] // bl))
    # The store starts at the first capacity that holds its first group.
    cap = rows_to_hold(
        default_pairs_capacity(cfg),
        min(nblocks, COLLECT_GROUP_BLOCKS) * per_block,
    )
    store = KVBatch.empty(cap, cfg.key_lanes)
    # [the store's fill, tokens dropped, keys cut]: on the DEVICE across a
    # group — an int() a block would serialize dispatch; the fill is read
    # once a group, the drops once.
    totals = jnp.zeros(3, jnp.int32)
    filled = grows = 0
    for g0 in range(0, nblocks, COLLECT_GROUP_BLOCKS):
        group = range(g0, min(g0 + COLLECT_GROUP_BLOCKS, nblocks))
        if g0:
            with obs.span("engine.sync", what="index.fill"):
                filled = int(totals[0])
        need = rows_to_hold(cap, filled + len(group) * per_block)
        if need != cap:
            with obs.span("index.grow", from_rows=cap, to_rows=need,
                          pairs=filled):
                store = programs.grow(store, rows=need)
            cap, grows = need, grows + 1
        with obs.span("index.map", blocks=len(group)):
            for b in group:
                blk, blk_ids = rows[b * bl : (b + 1) * bl], ids[b * bl : (b + 1) * bl]
                if blk.shape[0] < bl:  # the last block, padded with empty lines
                    pad = bl - blk.shape[0]
                    blk = np.concatenate(
                        [blk, np.zeros((pad, cfg.line_width), np.uint8)])
                    blk_ids = np.concatenate([blk_ids, np.zeros(pad, np.int32)])
                with obs.span("index.h2d", bytes=blk.nbytes + blk_ids.nbytes):
                    on_device = jax.device_put((blk, blk_ids))
                store, totals = programs.append(
                    store, totals, *programs.block(*on_device))
    with obs.span("index.collect", rows=cap):
        s_lanes, docs, entry_rows, n_live, n_entries = programs.collect(
            store, totals[0])
        with obs.span("engine.sync", what="index.entries"):
            n_entries = int(n_entries)
        word_cap = min(rows_to_hold(WORD_ROWS, n_entries), cap)
        words, offsets, postings, n_pairs, n_words = programs.cut(
            s_lanes, docs, entry_rows, n_live, rows=word_cap)
        with obs.span("engine.sync", what="index.collect"):
            n_pairs, n_words, (_, dropped, cut) = jax.tree.map(
                int, jax.device_get((n_pairs, n_words, tuple(totals))))
    if dropped:
        # Missing postings make a silently-wrong index; surface it loudly
        # (the WordCount per-line drop is reference semantics, but an index
        # user needs to know postings are absent).  The CLI also prints the
        # count on its result line.
        logger.warning(
            "inverted index dropped %d tokens beyond the %d-per-line cap; "
            "their postings are MISSING — raise emits_per_line",
            dropped, cfg.emits_per_line,
        )
    if pairs_capacity is not None and n_pairs > pairs_capacity:
        raise ValueError(
            f"distinct (word, doc) pairs ({n_pairs}) exceed pairs_capacity "
            f"({pairs_capacity}); pass a larger pairs_capacity, or none: the "
            "pair store grows"
        )
    with obs.span("index.d2h", bytes=4 * cap + (cfg.key_width + 4) * word_cap):
        postings, words, offsets = jax.device_get((postings, words, offsets))
    obs.metric_inc("index.pairs", n_pairs)
    # Runs of equal ids over the lines: the documents, for ids that follow
    # the lines (the CLI's i // lines_per_doc).
    obs.metric_inc("index.docs", int(np.count_nonzero(np.diff(ids))) + 1)
    obs.metric_inc("index.words", n_words)
    obs.metric_inc("index.hash_splits", n_entries - n_words)
    obs.metric_inc("index.dropped_tokens", dropped)
    obs.metric_inc("index.grows", grows)
    return Postings(
        words=words[:n_words],
        offsets=np.concatenate(
            [offsets[:n_words].astype(np.int64), [n_pairs]]),
        postings=postings[:n_pairs],
        dropped_tokens=dropped, cut_keys=cut, grows=grows, store_rows=cap,
    )


def build_inverted_index(
    lines: list[bytes] | np.ndarray,
    doc_ids: np.ndarray,
    cfg: EngineConfig | None = None,
    pairs_capacity: int | None = None,
) -> dict[bytes, list[int]]:
    """Host API: lines + per-line doc ids -> {word: sorted unique doc ids}:
    ``build_index``'s arrays as a dict, for callers that look words up."""
    return build_index(lines, doc_ids, cfg, pairs_capacity).to_dict()


class DistributedInvertedIndex:
    """Mesh-parallel inverted index.

    The same collective recipe as parallel/shuffle.DistributedMapReduce —
    hash-partition, equal bins, one ``lax.all_to_all`` per round, carried
    per-device state, lossless backlog retry — but the shuffled unit is the
    (word, doc) PAIR and the per-shard merge is a dedup, not a segment
    reduce.  Partitioning hashes the WORD only, so every posting of a word
    lands on one shard and host assembly is a plain per-shard union.
    """

    def __init__(
        self,
        mesh: jax.sharding.Mesh,
        cfg: EngineConfig,
        axis_name: str | None = None,
        skew_factor: float = 2.0,
        pairs_capacity: int | None = None,
    ):
        from jax.sharding import PartitionSpec as P

        from locust_tpu.parallel.mesh import DATA_AXIS
        from locust_tpu.parallel.shuffle import partition_to_bins, sized_bins

        axis = axis_name or DATA_AXIS
        self.mesh = mesh
        self.cfg = cfg
        self.axis = axis
        self.n_dev = mesh.shape[axis]
        self.bin_capacity = sized_bins(
            cfg.emits_per_block, self.n_dev, skew_factor
        )
        self.leftover_capacity = cfg.emits_per_block
        # Distinct (word, doc) pairs carried per shard; exceeding it raises
        # (a truncated index is silently wrong, like the single-device API).
        # Pairs accumulate across ALL rounds, so the floor is deliberately
        # larger than one round's emits.
        self.pairs_capacity = pairs_capacity or default_pairs_capacity(cfg, mult=4)
        self.max_drain_rounds = 2 + -(-cfg.emits_per_block // self.bin_capacity)
        max_drains = self.max_drain_rounds
        n_lanes = cfg.key_lanes

        def shuffle_round(local: KVBatch, acc: KVBatch, leftover: KVBatch):
            """One partition + all-to-all + dedup-merge; feed and drain
            share it (mirror of shuffle.DistributedMapReduce)."""
            send_lanes, send_vals, send_valid, shuf_ovf, new_leftover = (
                partition_to_bins(
                    KVBatch.concat(local, leftover),
                    self.n_dev,
                    self.bin_capacity,
                    leftover_capacity=self.leftover_capacity,
                )
            )
            recv_lanes = jax.lax.all_to_all(send_lanes, axis, 0, 0)
            recv_vals = jax.lax.all_to_all(send_vals, axis, 0, 0)
            recv_valid = jax.lax.all_to_all(send_valid, axis, 0, 0)
            received = KVBatch(
                key_lanes=recv_lanes.reshape(-1, n_lanes),
                values=recv_vals.reshape(-1),
                valid=recv_valid.reshape(-1),
            )
            merged, n_pairs = _dedup_sorted_pairs(
                _sort_pairs(KVBatch.concat(acc, received))
            )
            cap = self.pairs_capacity
            new_acc = KVBatch(
                key_lanes=merged.key_lanes[:cap],
                values=merged.values[:cap],
                valid=merged.valid[:cap],
            )
            # psum'd so every device sees the same value — the while_loop
            # below then takes the same trip count on all devices.
            backlog = jax.lax.psum(
                jnp.sum(new_leftover.valid.astype(jnp.int32)), axis
            )
            return new_acc, new_leftover, shuf_ovf, n_pairs, backlog

        def local_step(
            lines: jax.Array, doc_ids: jax.Array, acc: KVBatch, leftover: KVBatch
        ):
            """Feed + ON-DEVICE drain (lax.while_loop): one dispatch per
            round with no host sync, like DistributedMapReduce.local_step."""
            res = tokenize_block(lines, cfg)
            flat_keys = res.keys.reshape(-1, cfg.key_width)
            flat_valid = res.valid.reshape(-1)
            values = jnp.repeat(doc_ids.astype(jnp.int32), cfg.emits_per_line)
            batch = KVBatch.from_bytes(flat_keys, values, flat_valid)
            # Local pre-dedup: repeated (word, doc) pairs within the shard
            # collapse before touching the network (the combiner analog).
            local, _ = _dedup_sorted_pairs(_sort_pairs(batch))

            acc, leftover, shuf_ovf, n_pairs, backlog = shuffle_round(
                local, acc, leftover
            )
            zero_local = KVBatch.empty(local.size, n_lanes)

            def cond(state):
                _, _, _, _, backlog, drains = state
                return (backlog > 0) & (drains < max_drains)

            def body(state):
                acc, leftover, shuf_ovf, _, _, drains = state
                acc, leftover, so, n_pairs, backlog = shuffle_round(
                    zero_local, acc, leftover
                )
                return (acc, leftover, shuf_ovf + so, n_pairs, backlog,
                        drains + 1)

            acc, leftover, shuf_ovf, n_pairs, backlog, drains = (
                jax.lax.while_loop(
                    cond,
                    body,
                    (acc, leftover, shuf_ovf, n_pairs, backlog, jnp.int32(0)),
                )
            )
            stats = jnp.stack(
                [
                    jax.lax.psum(res.overflow, axis),
                    jax.lax.psum(shuf_ovf, axis),
                    jax.lax.pmax(n_pairs, axis),
                    backlog,
                    drains,
                ]
            )
            return acc, leftover, stats

        kv_spec = KVBatch(key_lanes=P(axis), values=P(axis), valid=P(axis))
        self._step = jax.jit(
            jax.shard_map(
                local_step,
                mesh=mesh,
                in_specs=(P(axis), P(axis), kv_spec, kv_spec),
                out_specs=(kv_spec, kv_spec, P()),
            )
        )
        # Across-round stats combiner, jitted ONCE per index builder:
        # overflows/drains ADD, worst-shard pairs MAX, backlog LAST.
        self._stats_merge = jax.jit(
            lambda a, b: jnp.stack(
                [a[0] + b[0], a[1] + b[1], jnp.maximum(a[2], b[2]), b[3],
                 a[4] + b[4]]
            )
        )

    @property
    def lines_per_round(self) -> int:
        return self.n_dev * self.cfg.block_lines

    def run(
        self,
        lines: list[bytes] | np.ndarray,
        doc_ids: np.ndarray,
        stats_sync_every: int = 16,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
    ) -> dict[bytes, list[int]]:
        cfg = self.cfg
        if not isinstance(lines, np.ndarray):
            rows = bytes_ops.strings_to_rows(list(lines), cfg.line_width)
        else:
            rows = lines
        ids = np.asarray(doc_ids, np.int32)
        if rows.shape[0] != ids.shape[0]:
            raise ValueError(f"{rows.shape[0]} lines but {ids.shape[0]} doc ids")

        lpr = self.lines_per_round
        nrounds = max(1, -(-rows.shape[0] // lpr))
        chunks = (
            (rows[r * lpr : (r + 1) * lpr], ids[r * lpr : (r + 1) * lpr])
            for r in range(nrounds)
        )
        fingerprint = None
        if checkpoint_dir is not None:
            from locust_tpu.io.serde import fingerprint_corpus

            # Doc ids are part of the corpus identity: the same lines with
            # different sharding produce a different index.
            fingerprint = fingerprint_corpus(
                rows, doc_ids=fingerprint_corpus(ids), **self._identity()
            )
        return self._run_rounds(
            chunks,
            stats_sync_every,
            fingerprint=fingerprint,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
        )

    def _identity(self) -> dict:
        """Engine/pipeline/mesh identity bound into checkpoint
        fingerprints (shuffle.DistributedMapReduce._identity mirror)."""
        return dict(
            engine="inverted_index",
            cfg=repr(self.cfg),
            mesh=f"{self.n_dev}x{self.axis}",
            bin_capacity=self.bin_capacity,
            pairs_capacity=self.pairs_capacity,
        )

    def run_stream(
        self,
        blocks,
        stats_sync_every: int = 16,
        fingerprint: str | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
    ) -> dict[bytes, list[int]]:
        """Bounded-memory variant: ``blocks`` yields
        ``(rows [<=lines_per_round, width], doc_ids [same length])`` chunk
        pairs — e.g. zip a ``StreamingCorpus(..., block_lines=
        self.lines_per_round)`` with a doc-id generator.  Only one chunk
        plus the sharded pair table are ever resident.  Pass a corpus
        ``fingerprint`` to enable checkpoint/resume.
        """
        from locust_tpu.io.loader import prefetch_blocks
        from locust_tpu.parallel.shuffle import stream_checkpoint_fingerprint

        fingerprint = stream_checkpoint_fingerprint(
            fingerprint, checkpoint_dir, self._identity()
        )
        return self._run_rounds(
            prefetch_blocks(blocks),
            stats_sync_every,
            fingerprint=fingerprint,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
        )

    def _run_rounds(
        self,
        chunk_iter,
        stats_sync_every: int,
        fingerprint: str | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
    ):
        from jax.sharding import PartitionSpec as P

        from locust_tpu.parallel.mesh import shard_rows
        from locust_tpu.parallel.shuffle import (
            ShardedCheckpoint,
            _gather_batch_host,
            drive_checkpointed_rounds,
        )

        cfg = self.cfg
        lpr = self.lines_per_round
        width = cfg.line_width

        sharding = jax.sharding.NamedSharding(self.mesh, P(self.axis))
        acc = jax.device_put(
            KVBatch.empty(self.n_dev * self.pairs_capacity, cfg.key_lanes), sharding
        )
        leftover = jax.device_put(
            KVBatch.empty(self.n_dev * self.leftover_capacity, cfg.key_lanes),
            sharding,
        )

        # Drains run ON DEVICE inside the step; the host only folds stats
        # in every ``stats_sync_every`` rounds, so round dispatch pipelines
        # (same RoundStats protocol as DistributedMapReduce.run).
        n_pairs = 0
        shuf_ovf = 0
        emit_ovf = 0
        start_round = 0

        ckpt = None
        if checkpoint_dir is not None:
            ckpt = ShardedCheckpoint(
                checkpoint_dir, fingerprint, sharding,
                async_writes=cfg.async_checkpoint,
            )
            restored = ckpt.load()
            if restored is not None:
                start_round, extras, acc, leftover = restored
                n_pairs = int(extras["n_pairs"])
                shuf_ovf = int(extras["shuf_ovf"])
                emit_ovf = int(extras["emit_ovf"])

        def snapshot(next_round: int) -> None:
            ckpt.snapshot(
                next_round,
                acc,
                leftover,
                n_pairs=np.int64(n_pairs),
                shuf_ovf=np.int64(shuf_ovf),
                emit_ovf=np.int64(emit_ovf),
            )

        def on_sync(st) -> None:
            nonlocal n_pairs, shuf_ovf, emit_ovf
            emit_ovf += int(st[0])
            shuf_ovf += int(st[1])
            n_pairs = max(n_pairs, int(st[2]))
            backlog = int(st[3])
            if backlog > 0:
                raise RuntimeError(
                    f"index backlog failed to drain in "
                    f"{self.max_drain_rounds} rounds ({backlog} pairs "
                    "remain); raise skew_factor"
                )
            if shuf_ovf:
                raise RuntimeError(
                    f"index shuffle lost {shuf_ovf} pairs; "
                    "emits exceeded cfg.emits_per_block"
                )

        from locust_tpu.parallel.shuffle import RoundStats

        round_stats = RoundStats(self._stats_merge, on_sync, stats_sync_every)
        from locust_tpu.parallel.shuffle import normalize_round_chunk

        def fold_round(chunk) -> None:
            nonlocal acc, leftover
            rows_chunk, ids_chunk = chunk
            ids_chunk = np.asarray(ids_chunk, dtype=np.int32)
            rows_chunk = np.asarray(rows_chunk, dtype=np.uint8)
            if rows_chunk.shape[0] != ids_chunk.shape[0]:
                raise ValueError(
                    f"chunk has {rows_chunk.shape[0]} lines but "
                    f"{ids_chunk.shape[0]} doc ids"
                )
            rows_chunk = normalize_round_chunk(rows_chunk, lpr, width)
            if ids_chunk.shape[0] < lpr:
                ids_chunk = np.concatenate(
                    [ids_chunk, np.zeros(lpr - ids_chunk.shape[0], np.int32)]
                )
            acc, leftover, stats = self._step(
                shard_rows(rows_chunk, self.mesh, self.axis),
                shard_rows(ids_chunk, self.mesh, self.axis),
                acc,
                leftover,
            )
            round_stats.push(stats)

        drive_checkpointed_rounds(
            chunk_iter, fold_round, round_stats, ckpt, snapshot,
            checkpoint_every, start_round,
        )
        if emit_ovf:
            # Missing postings make a silently-wrong index; unlike WordCount
            # (whose per-line cap is reference semantics, main.cu:141-144),
            # surface it loudly.
            logger.warning(
                "inverted index dropped %d tokens beyond the %d-per-line "
                "cap; their postings are MISSING — raise emits_per_line",
                emit_ovf,
                cfg.emits_per_line,
            )
        if n_pairs > self.pairs_capacity:
            raise ValueError(
                f"distinct (word, doc) pairs per shard ({n_pairs}) exceed "
                f"pairs_capacity ({self.pairs_capacity}); pass a larger one"
            )

        # Host assembly: shards are disjoint by word (hash partition) and
        # internally (hash, doc)-sorted + deduped, so a plain grouping union
        # yields ascending unique doc ids per word.
        out: dict[bytes, list[int]] = {}
        for k, v in _gather_batch_host(acc).to_host_pairs():
            out.setdefault(k, []).append(int(v))
        return out


def build_inverted_index_mesh(
    lines: list[bytes] | np.ndarray,
    doc_ids: np.ndarray,
    mesh: jax.sharding.Mesh,
    cfg: EngineConfig | None = None,
    **kw,
) -> dict[bytes, list[int]]:
    """Mesh convenience wrapper: build the index across all devices."""
    return DistributedInvertedIndex(mesh, cfg or EngineConfig(), **kw).run(
        lines, doc_ids
    )
