"""TPC-C New-Order and Payment as stored procedures on the versioned KV
data plane (TPC Benchmark C Standard Specification rev. 5.11, clauses 2.4
and 2.5).

A procedure txn is the feature a Cassandra user reaches Accord for
(``UPDATE ... SET x = x - 5`` inside ``BEGIN TRANSACTION``): its writes are
COMPUTED from its own reads.  The coordinator reads the declared rows at
``executeAt``, evaluates the procedure once over what it read
(``ProcUpdate.apply``), and the replicas apply the versions it wrote;
``Txn.execute`` hands the read ``Data`` to ``Update.apply`` exactly as
upstream's ``Txn.java`` does.  The evaluation is deterministic in the reads
and the arguments, so a recovery coordinator that re-executes the txn, or a
replica that replays its journal, computes byte-equal writes.

Rows.  A row is a register: every write appends a new VERSION to the token's
append log (``KVDataStore``), and a read returns the newest version of the
row below ``executeAt`` (``KVDataStore.read_row``).  A version is a tuple
``(row_id, tag, *columns)``: ``row_id`` names the row (``"S/1/4711"``: table
and primary key), ``tag`` the txn that wrote it (the client's number for
the attempt; 0 for the population).  A row's key is
``blake2b(row_id) mod 2**32``; two rows that share a token share its log and
are told apart by ``row_id``, as Cassandra tells two partitions with one
token apart by their keys.  The ORDER, NEW-ORDER and HISTORY rows a txn
inserts are keyed by an ``O_ID`` it only learns by reading ``D_NEXT_O_ID``,
and Accord declares keys up front, so each is an ENTRY appended to a
per-district list row (``"O/w/d"``, ``"NO/w/d"``, ``"H/w/d"``); an ORDER
carries its ORDER-LINEs.  (Loaded ORDERs, whose ids the loader knows, may
be rows of their own, ``"O/w/d/o"``: these procedures never read them.)

Columns, in storage order (money in cents, rates in 1/10000):

    W  name street_1 street_2 city state zip tax ytd
    D  name street_1 street_2 city state zip tax ytd next_o_id
    C  first middle last street_1 street_2 city state zip phone since credit
       credit_lim discount balance ytd_payment payment_cnt delivery_cnt data
    I  im_id name price data
    S  quantity dist_01 .. dist_10 ytd order_cnt remote_cnt data
    O  o_id c_id entry_d carrier_id ol_cnt all_local lines
       (a line: number i_id supply_w_id delivery_d quantity amount dist_info)
    NO o_id
    H  c_id c_d_id c_w_id d_id w_id date amount data

The client op is ``["call", <procedure>, args]`` (maelstrom/node.py); the
reply carries the procedure's outputs and, for every row it read, the tag
of the version it saw (None for a row that does not exist).  Departures
from the spec: Payment selects the customer by id; the dates are the
client's (``args["date"]``), so that re-execution is deterministic; money
and rates are fixed point, the total rounded half up to the cent.
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Optional, Tuple

from .. import api
from ..primitives.keys import IntKey, Keys, Ranges
from ..primitives.timestamp import TxnKind
from ..primitives.txn import Txn
from ..utils import async_chain
from .kvstore import KVData, KVWrite

TOKEN_SPACE = 1 << 32
# an argument's magnitude bound: sums of a run's amounts stay in 64 bits
ARG_BOUND = 1 << 31
# a New-Order's most lines (clause 2.4.1.3: ol_cnt 5 .. 15)
MAX_LINES = 15

# storage column indices (after row_id, tag)
W_NAME, W_TAX, W_YTD = 2, 8, 9
D_NAME, D_TAX, D_YTD, D_NEXT_O_ID = 2, 8, 9, 10
(C_FIRST, C_MIDDLE, C_LAST, C_STREET_1, C_PHONE, C_SINCE, C_CREDIT,
 C_CREDIT_LIM, C_DISCOUNT, C_BALANCE, C_YTD_PAYMENT, C_PAYMENT_CNT,
 C_DATA) = 2, 3, 4, 5, 10, 11, 12, 13, 14, 15, 16, 17, 19
I_NAME, I_PRICE, I_DATA = 3, 4, 5
S_QUANTITY, S_YTD, S_ORDER_CNT, S_REMOTE_CNT, S_DATA = 2, 13, 14, 15, 16
ADDRESS = slice(3, 8)        # street_1 .. zip of W, D and C (C: 5..10)


def row_token(row_id: str) -> int:
    """The row's key on the token ring."""
    digest = hashlib.blake2b(row_id.encode(), digest_size=8).digest()
    return int.from_bytes(digest, "big") % TOKEN_SPACE


def _money(cents: int) -> str:
    return f"{cents // 100}.{cents % 100:02d}"


def _int(value, what: str) -> None:
    if type(value) is not int or not -ARG_BOUND < value < ARG_BOUND:
        raise ValueError(f"{what} must be an int of magnitude below 2**31, "
                         f"not {value!r}")


def _check_ints(args, names) -> None:
    for name in names:
        if name not in args:
            raise ValueError(f"missing argument {name!r}")
        _int(args[name], repr(name))


def _version(row: tuple, tag: int, **cols) -> tuple:
    """A new version of ``row`` by ``tag`` with columns replaced
    (``cols``: index -> value, spelt ``_<index>``)."""
    out = list(row)
    out[1] = tag
    for k, v in cols.items():
        out[int(k[1:])] = v
    return tuple(out)


# ---------------------------------------------------------------------------
# the procedures: rows declared up front, then one evaluation over the reads
# ---------------------------------------------------------------------------

class NewOrder:
    """Clause 2.4.2.2.  args: ``w``, ``d``, ``c``, ``lines`` ([i_id,
    supply_w_id, quantity] each), ``date``, ``tag``.  An item id that names
    no ITEM row rolls the txn back: it writes nothing and commits as a
    no-op (Accord has no abort)."""

    @staticmethod
    def check(args) -> None:
        _check_ints(args, ("w", "d", "c", "date", "tag"))
        lines = args.get("lines")
        if not isinstance(lines, (list, tuple)) \
                or not 1 <= len(lines) <= MAX_LINES:
            raise ValueError(f"'lines' must be a list of 1 to {MAX_LINES} "
                             f"lines, not {lines!r}")
        for line in lines:
            if not isinstance(line, (list, tuple)) or len(line) != 3:
                raise ValueError(f"a line is [i_id, supply_w_id, quantity], "
                                 f"not {line!r}")
            for value in line:
                _int(value, "each of a line's [i_id, supply_w_id, quantity]")

    @staticmethod
    def rows(args) -> Tuple[List[str], List[str]]:
        w, d = args["w"], args["d"]
        items = [f"I/{i}" for i, _sw, _q in args["lines"]]
        stock = [f"S/{sw}/{i}" for i, sw, _q in args["lines"]]
        reads = [f"W/{w}", f"D/{w}/{d}", f"C/{w}/{d}/{args['c']}",
                 *items, *stock]
        writes = [f"D/{w}/{d}", *stock, f"O/{w}/{d}", f"NO/{w}/{d}"]
        return reads, writes

    @staticmethod
    def evaluate(args, reads: Dict[str, Optional[tuple]]):
        w_id, d_id, c_id, tag = args["w"], args["d"], args["c"], args["tag"]
        wh = reads.get(f"W/{w_id}")
        dist = reads.get(f"D/{w_id}/{d_id}")
        cust = reads.get(f"C/{w_id}/{d_id}/{c_id}")
        if wh is None or dist is None or cust is None:
            return {}, {"error": "no such warehouse, district or customer"}
        out = {"w_id": w_id, "d_id": d_id, "c_id": c_id,
               "c_last": cust[C_LAST], "c_credit": cust[C_CREDIT],
               "o_id": dist[D_NEXT_O_ID]}
        lines = args["lines"]
        if any(reads.get(f"I/{i}") is None
               or reads.get(f"S/{sw}/{i}") is None for i, sw, _q in lines):
            out["rollback"] = "Item number is not valid"
            return {}, out
        o_id = dist[D_NEXT_O_ID]
        writes = {f"D/{w_id}/{d_id}": _version(dist, tag,
                                               _10=o_id + 1)}
        stock: Dict[str, tuple] = {}
        all_local = int(all(sw == w_id for _i, sw, _q in lines))
        rows_out, stored, total = [], [], 0
        for number, (i_id, sw, qty) in enumerate(lines, 1):
            item = reads[f"I/{i_id}"]
            rid = f"S/{sw}/{i_id}"
            s = stock.get(rid, reads[rid])
            s_qty = s[S_QUANTITY]
            s_qty = s_qty - qty if s_qty >= qty + 10 else s_qty - qty + 91
            s = _version(s, tag, _2=s_qty, _13=s[S_YTD] + qty,
                         _14=s[S_ORDER_CNT] + 1,
                         _15=s[S_REMOTE_CNT] + int(sw != w_id))
            stock[rid] = s
            amount = qty * item[I_PRICE]
            total += amount
            brand = ("B" if "ORIGINAL" in item[I_DATA]
                     and "ORIGINAL" in s[S_DATA] else "G")
            stored.append((number, i_id, sw, None, qty, amount,
                           s[2 + d_id]))
            rows_out.append([sw, i_id, item[I_NAME], qty, s_qty, brand,
                             item[I_PRICE], amount])
        writes.update(stock)
        writes[f"O/{w_id}/{d_id}"] = (f"O/{w_id}/{d_id}", tag, o_id, c_id,
                                      args["date"], None, len(lines),
                                      all_local, tuple(stored))
        writes[f"NO/{w_id}/{d_id}"] = (f"NO/{w_id}/{d_id}", tag, o_id)
        scale = ((10000 - cust[C_DISCOUNT])
                 * (10000 + wh[W_TAX] + dist[D_TAX]))
        out.update(c_discount=cust[C_DISCOUNT], w_tax=wh[W_TAX],
                   d_tax=dist[D_TAX], o_ol_cnt=len(lines),
                   o_entry_d=args["date"], lines=rows_out,
                   total_amount=(total * scale + 50_000_000) // 100_000_000)
        return writes, out


class Payment:
    """Clause 2.5.2.2, the customer selected by id.  args: ``w``, ``d``,
    ``c_w``, ``c_d``, ``c``, ``amount`` (cents), ``date``, ``tag``."""

    @staticmethod
    def check(args) -> None:
        _check_ints(args, ("w", "d", "c_w", "c_d", "c", "amount", "date",
                           "tag"))

    @staticmethod
    def rows(args) -> Tuple[List[str], List[str]]:
        w, d = args["w"], args["d"]
        cust = f"C/{args['c_w']}/{args['c_d']}/{args['c']}"
        return ([f"W/{w}", f"D/{w}/{d}", cust],
                [f"W/{w}", f"D/{w}/{d}", cust, f"H/{w}/{d}"])

    @staticmethod
    def evaluate(args, reads: Dict[str, Optional[tuple]]):
        w_id, d_id, tag, amount = args["w"], args["d"], args["tag"], \
            args["amount"]
        c_w, c_d, c_id = args["c_w"], args["c_d"], args["c"]
        wh = reads.get(f"W/{w_id}")
        dist = reads.get(f"D/{w_id}/{d_id}")
        cust = reads.get(f"C/{c_w}/{c_d}/{c_id}")
        if wh is None or dist is None or cust is None:
            return {}, {"error": "no such warehouse, district or customer"}
        balance = cust[C_BALANCE] - amount
        c_data = cust[C_DATA]
        if cust[C_CREDIT] == "BC":
            c_data = (f"{c_id} {c_d} {c_w} {d_id} {w_id} {_money(amount)}|"
                      + c_data)[:500]
        writes = {
            f"W/{w_id}": _version(wh, tag, _9=wh[W_YTD] + amount),
            f"D/{w_id}/{d_id}": _version(dist, tag, _9=dist[D_YTD] + amount),
            f"C/{c_w}/{c_d}/{c_id}": _version(
                cust, tag, _15=balance,
                _16=cust[C_YTD_PAYMENT] + amount,
                _17=cust[C_PAYMENT_CNT] + 1, _19=c_data),
            f"H/{w_id}/{d_id}": (f"H/{w_id}/{d_id}", tag, c_id, c_d, c_w,
                                 d_id, w_id, args["date"], amount,
                                 wh[W_NAME] + "    " + dist[D_NAME]),
        }
        out = {"w_id": w_id, "d_id": d_id, "c_id": c_id, "c_d_id": c_d,
               "c_w_id": c_w, "h_amount": amount, "h_date": args["date"],
               "w_address": list(wh[ADDRESS]),
               "d_address": list(dist[ADDRESS]),
               "c_first": cust[C_FIRST], "c_middle": cust[C_MIDDLE],
               "c_last": cust[C_LAST], "c_address": list(cust[5:10]),
               "c_phone": cust[C_PHONE], "c_since": cust[C_SINCE],
               "c_credit": cust[C_CREDIT], "c_credit_lim": cust[C_CREDIT_LIM],
               "c_discount": cust[C_DISCOUNT], "c_balance": balance}
        if cust[C_CREDIT] == "BC":
            out["c_data"] = c_data[:200]
        return writes, out


PROCEDURES = {"new_order": NewOrder, "payment": Payment}


# ---------------------------------------------------------------------------
# the data-plane SPI
# ---------------------------------------------------------------------------

def _by_token(row_ids) -> Dict[int, tuple]:
    out: Dict[int, list] = {}
    for rid in row_ids:
        out.setdefault(row_token(rid), []).append(rid)
    return {t: tuple(sorted(set(rids))) for t, rids in sorted(out.items())}


def _slice_rows(rows: Dict[int, tuple], ranges: Ranges) -> Dict[int, tuple]:
    return {t: r for t, r in rows.items() if ranges.contains_token(t)}


def _merge_rows(a: Dict[int, tuple], b: Dict[int, tuple]) -> Dict[int, tuple]:
    out = dict(a)
    for t, rids in b.items():
        out[t] = tuple(sorted(set(out.get(t, ())) | set(rids)))
    return dict(sorted(out.items()))


class RowRead(api.Read):
    """The newest version of each named row below ``executeAt``;
    ``rows``: token -> row ids on it."""

    def __init__(self, rows: Dict[int, tuple]):
        self.rows = rows

    def keys(self) -> Keys:
        return Keys([IntKey(t) for t in self.rows])

    def read(self, key, safe_store, execute_at, store):
        t = key.token()
        return async_chain.success(KVData(
            {rid: store.read_row(t, rid, execute_at)
             for rid in self.rows.get(t, ())}))

    def slice(self, ranges: Ranges) -> "RowRead":
        return RowRead(_slice_rows(self.rows, ranges))

    def merge(self, other: Optional["RowRead"]) -> "RowRead":
        if other is None:
            return self
        return RowRead(_merge_rows(self.rows, other.rows))


def _outcome(update: "ProcUpdate", data) -> tuple:
    """(writes, outputs) of the procedure over ``data``: evaluated once a
    data object, for ``Update.apply`` and ``Query.compute`` alike."""
    cached = getattr(data, "_proc", None)
    if cached is not None and cached[0] is update:
        return cached[1]
    reads = data.values if data is not None else {}
    outcome = PROCEDURES[update.name].evaluate(update.args, reads)
    if data is not None:
        data._proc = (update, outcome)
    return outcome


class ProcUpdate(api.Update):
    """A procedure's write half: its name, its arguments, and the rows it
    may write (token -> row ids).  ``apply`` evaluates the procedure over
    the txn's reads; the versions it wrote become the appends of a
    ``KVWrite``, grouped by token."""

    is_procedure = True

    def __init__(self, name: str, args: dict, rows: Dict[int, tuple]):
        self.name = name
        self.args = args
        self.rows = rows

    def keys(self) -> Keys:
        return Keys([IntKey(t) for t in self.rows])

    def apply(self, execute_at, data) -> KVWrite:
        writes, _out = _outcome(self, data)
        appends: Dict[int, tuple] = {}
        for rid, version in sorted(writes.items()):
            t = row_token(rid)
            appends[t] = appends.get(t, ()) + (version,)
        return KVWrite(appends)

    def slice(self, ranges: Ranges) -> "ProcUpdate":
        return ProcUpdate(self.name, self.args,
                          _slice_rows(self.rows, ranges))

    def merge(self, other: Optional["ProcUpdate"]) -> "ProcUpdate":
        if other is None:
            return self
        return ProcUpdate(self.name, self.args,
                          _merge_rows(self.rows, other.rows))


class ProcResult(api.Result):
    """What the client is answered: the procedure's outputs and, per row
    read, the tag of the version seen (None: no such row)."""

    def __init__(self, out: dict, seen: Dict[str, Optional[int]]):
        self.out = out
        self.seen = seen

    def __repr__(self):
        return f"ProcResult(out={self.out}, seen={self.seen})"


class ProcQuery(api.Query):
    def compute(self, txn_id, execute_at, keys, data, read,
                update) -> ProcResult:
        _writes, out = _outcome(update, data)
        values = data.values if data is not None else {}
        return ProcResult(out, {rid: (v[1] if v is not None else None)
                                for rid, v in sorted(values.items())})


def procedure_txn(name: str, args: dict) -> Txn:
    """The Txn of one procedure call over its declared rows.  Raises
    ValueError for an unknown procedure or arguments outside its schema,
    before anything is coordinated: an evaluation that raised would do so
    on every replica that executes the txn, recovery's included, and the
    txns behind it on the same rows would wait for it forever."""
    if not isinstance(name, str) or name not in PROCEDURES:
        raise ValueError(f"unknown procedure {name!r}")
    if not isinstance(args, dict):
        raise ValueError(f"arguments are an object, not {args!r}")
    proc = PROCEDURES[name]
    proc.check(args)
    reads, writes = proc.rows(args)
    read_rows, write_rows = _by_token(reads), _by_token(writes)
    keys = Keys([IntKey(t) for t in sorted(set(read_rows) | set(write_rows))])
    return Txn(TxnKind.Write, keys, RowRead(read_rows),
               ProcUpdate(name, args, write_rows), ProcQuery())
