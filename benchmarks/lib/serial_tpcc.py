"""serial_tpcc: the plain reference of TPC-C New-Order and Payment.

TPC Benchmark C Standard Specification rev. 5.11 (TPC, 2010): the
population of clause 4.3.3.1, the New-Order (clause 2.4.2.2) and Payment
(clause 2.5.2.2) transactions run one at a time over dicts, the consistency
conditions 1-4 of clause 3.3.2, and the replay that holds a concurrent run
to them.  It imports nothing of the system it checks.

The tables.  ``state`` maps a row id to the list of its values, oldest
first: a register row (WAREHOUSE ``"W/w"``, DISTRICT ``"D/w/d"``, CUSTOMER
``"C/w/d/c"``, ITEM ``"I/i"``, STOCK ``"S/w/i"``) holds its versions, the
last is current; a list row (ORDER ``"O/w/d"``, NEW-ORDER ``"NO/w/d"``,
HISTORY ``"H/w/d"``) holds its entries, since the ``O_ID`` of an ORDER a
New-Order inserts is only known once ``D_NEXT_O_ID`` is read.  The
population's ORDERs, whose ids the loader knows, are register rows
``"O/w/d/o"``; both forms carry each ORDER's ORDER-LINEs.  A value is a
tuple ``(row_id, tag, *columns)``: ``tag`` names the txn that wrote it (0:
the population).  The columns, in order (money in cents, rates in 1/10000,
dates in seconds):

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

Departures from the spec, each the configuration's: the customer of a
Payment is selected by id (the spec: 60 % by last name); the txn's date is
an argument (the terminal's clock), so that a txn's outputs are a function
of its inputs and the rows it reads; money and rates are fixed point, a
New-Order's total rounded half up to the cent; a BC customer's ``C_DATA``
takes ``"c_id c_d_id c_w_id d_id w_id amount|"`` at its left, cut to 500
characters; the generated strings are slices of one seeded pool of
alphanumerics, of the spec's lengths."""

import json
import random

from . import serial_kv

POPULATED_AT = 1_262_304_000          # the population's "current date"
UNUSED_ITEM = 0                       # an item id no ITEM row has (2.4.1.5)
_SYLLABLES = ("BAR", "OUGHT", "ABLE", "PRI", "PRES", "ESE", "ANTI", "CALLY",
              "ATION", "EING")
_ALNUM = b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789"

# column indices (after row_id, tag)
W_NAME, W_TAX, W_YTD = 2, 8, 9
D_NAME, D_TAX, D_YTD, D_NEXT_O_ID = 2, 8, 9, 10
(C_FIRST, C_MIDDLE, C_LAST, C_PHONE, C_SINCE, C_CREDIT, C_CREDIT_LIM,
 C_DISCOUNT, C_BALANCE, C_YTD_PAYMENT, C_PAYMENT_CNT, C_DATA) = (
    2, 3, 4, 10, 11, 12, 13, 14, 15, 16, 17, 19)
I_NAME, I_PRICE, I_DATA = 3, 4, 5
S_QUANTITY, S_YTD, S_ORDER_CNT, S_REMOTE_CNT, S_DATA = 2, 13, 14, 15, 16
O_ID, O_OL_CNT, O_LINES = 2, 6, 8


class NotTPCC(AssertionError):
    """The run is not explained by the reference: ``kind`` is serial_kv's,
    or ``result-mismatch``, ``row-mismatch``, ``consistency``."""

    def __init__(self, kind, text):
        super().__init__(f"{kind}: {text}")
        self.kind = kind


def last_name(n):
    """Clause 4.3.2.3: three syllables by the digits of ``n`` (0-999)."""
    return (_SYLLABLES[n // 100] + _SYLLABLES[n // 10 % 10]
            + _SYLLABLES[n % 10])


def nurand(rng, a, x, y, c):
    """Clause 2.1.6: NURand(A, x, y) with run constant ``c``."""
    return (((rng.randint(0, a) | rng.randint(x, y)) + c)
            % (y - x + 1)) + x


class _Strings:
    """Seeded random strings of the spec's lengths: slices of one pool."""

    def __init__(self, rng, size=1 << 22):
        table = bytes(_ALNUM[b % len(_ALNUM)] for b in range(256))
        self.pool = rng.randbytes(size).translate(table).decode("ascii")
        self.digits = "".join(str(b % 10) for b in rng.randbytes(1 << 16))
        self.rng = rng

    def a(self, lo, hi=None):
        n = lo if hi is None else self.rng.randint(lo, hi)
        at = self.rng.randrange(len(self.pool) - n)
        return self.pool[at:at + n]

    def n(self, k):
        at = self.rng.randrange(len(self.digits) - k)
        return self.digits[at:at + k]

    def data(self):
        """I_DATA / S_DATA: 26-50 characters, 10 % holding ORIGINAL."""
        s = self.a(26, 50)
        if self.rng.random() < 0.1:
            at = self.rng.randint(0, len(s) - 8)
            s = s[:at] + "ORIGINAL" + s[at + 8:]
        return s

    def address(self):
        return (self.a(10, 20), self.a(10, 20), self.a(10, 20), self.a(2),
                self.n(4) + "11111")


def populate(seed, items=100_000, districts=10, customers=3000,
             orders=3000, new_orders=900):
    """Clause 4.3.3.1 at W = 1: ``{row_id: [value]}``, one list a row."""
    rng = random.Random(f"tpcc-population/{seed}")
    s = _Strings(rng)
    w = 1
    state = {}
    for i in range(1, items + 1):
        state[f"I/{i}"] = [(f"I/{i}", 0, rng.randint(1, 10_000), s.a(14, 24),
                            rng.randint(100, 10_000), s.data())]
    state[f"W/{w}"] = [(f"W/{w}", 0, s.a(6, 10), *s.address(),
                        rng.randint(0, 2000), 30_000_000)]
    for i in range(1, items + 1):
        state[f"S/{w}/{i}"] = [(f"S/{w}/{i}", 0, rng.randint(10, 100),
                                *(s.a(24) for _ in range(10)), 0, 0, 0,
                                s.data())]
    c_load = rng.randint(0, 255)
    for d in range(1, districts + 1):
        state[f"D/{w}/{d}"] = [(f"D/{w}/{d}", 0, s.a(6, 10), *s.address(),
                                rng.randint(0, 2000), 3_000_000,
                                orders + 1)]
        history = []
        for c in range(1, customers + 1):
            rid = f"C/{w}/{d}/{c}"
            last = last_name(c - 1 if c <= 1000
                             else nurand(rng, 255, 0, 999, c_load))
            state[rid] = [(rid, 0, s.a(8, 16), "OE", last, *s.address(),
                           s.n(16), POPULATED_AT,
                           "BC" if rng.random() < 0.1 else "GC", 5_000_000,
                           rng.randint(0, 5000), -1000, 1000, 1, 0,
                           s.a(300, 500))]
            history.append((f"H/{w}/{d}", 0, c, d, w, d, w, POPULATED_AT,
                            1000, s.a(12, 24)))
        state[f"H/{w}/{d}"] = history
        who = list(range(1, customers + 1))
        rng.shuffle(who)
        delivered = orders - new_orders
        for o in range(1, orders + 1):
            lines = tuple(
                (n, rng.randint(1, items), w,
                 POPULATED_AT if o <= delivered else None, 5,
                 0 if o <= delivered else rng.randint(1, 999_999), s.a(24))
                for n in range(1, rng.randint(5, 15) + 1))
            state[f"O/{w}/{d}/{o}"] = [(
                f"O/{w}/{d}/{o}", 0, o, who[(o - 1) % customers],
                POPULATED_AT, rng.randint(1, 10) if o <= delivered else None,
                len(lines), 1, lines)]
        state[f"O/{w}/{d}"] = []
        state[f"NO/{w}/{d}"] = [(f"NO/{w}/{d}", 0, o)
                                for o in range(delivered + 1, orders + 1)]
    return state


def _version(row, tag, changes):
    out = list(row)
    out[1] = tag
    for i, v in changes.items():
        out[i] = v
    return tuple(out)


def _money(cents):
    return f"{cents // 100}.{cents % 100:02d}"


# -- the two transactions ---------------------------------------------------

def rows_of(name, args):
    """(rows read, rows written) as the procedure declares them."""
    w, d = args["w"], args["d"]
    if name == "new_order":
        lines = args["lines"]
        stock = [f"S/{sw}/{i}" for i, sw, _q in lines]
        return ([f"W/{w}", f"D/{w}/{d}", f"C/{w}/{d}/{args['c']}",
                 *(f"I/{i}" for i, _sw, _q in lines), *stock],
                [f"D/{w}/{d}", *stock, f"O/{w}/{d}", f"NO/{w}/{d}"])
    cust = f"C/{args['c_w']}/{args['c_d']}/{args['c']}"
    return ([f"W/{w}", f"D/{w}/{d}", cust],
            [f"W/{w}", f"D/{w}/{d}", cust, f"H/{w}/{d}"])


def new_order(state, args):
    """Clause 2.4.2.2 on ``state`` (changed in place); its outputs."""
    w, d, c, tag = args["w"], args["d"], args["c"], args["tag"]
    wh, dist = state[f"W/{w}"][-1], state[f"D/{w}/{d}"][-1]
    cust = state[f"C/{w}/{d}/{c}"][-1]
    out = {"w_id": w, "d_id": d, "c_id": c, "c_last": cust[C_LAST],
           "c_credit": cust[C_CREDIT], "o_id": dist[D_NEXT_O_ID]}
    lines = args["lines"]
    if any(f"I/{i}" not in state or f"S/{sw}/{i}" not in state
           for i, sw, _q in lines):
        out["rollback"] = "Item number is not valid"     # nothing written
        return out
    o_id = dist[D_NEXT_O_ID]
    state[f"D/{w}/{d}"].append(_version(dist, tag, {D_NEXT_O_ID: o_id + 1}))
    rows, stored, total = [], [], 0
    for number, (i, sw, qty) in enumerate(lines, 1):
        item = state[f"I/{i}"][-1]
        stock = state[f"S/{sw}/{i}"][-1]
        q = stock[S_QUANTITY]
        q = q - qty if q >= qty + 10 else q - qty + 91
        stock = _version(stock, tag, {
            S_QUANTITY: q, S_YTD: stock[S_YTD] + qty,
            S_ORDER_CNT: stock[S_ORDER_CNT] + 1,
            S_REMOTE_CNT: stock[S_REMOTE_CNT] + (sw != w)})
        chain = state[f"S/{sw}/{i}"]
        if chain[-1][1] == tag:          # the same item twice in one order
            chain[-1] = stock
        else:
            chain.append(stock)
        amount = qty * item[I_PRICE]
        total += amount
        brand = "B" if ("ORIGINAL" in item[I_DATA]
                        and "ORIGINAL" in stock[S_DATA]) else "G"
        stored.append((number, i, sw, None, qty, amount, stock[2 + d]))
        rows.append([sw, i, item[I_NAME], qty, q, brand, item[I_PRICE],
                     amount])
    state[f"O/{w}/{d}"].append((f"O/{w}/{d}", tag, o_id, c, args["date"],
                                None, len(lines),
                                int(all(sw == w for _i, sw, _q in lines)),
                                tuple(stored)))
    state[f"NO/{w}/{d}"].append((f"NO/{w}/{d}", tag, o_id))
    scale = (10000 - cust[C_DISCOUNT]) * (10000 + wh[W_TAX] + dist[D_TAX])
    out.update(c_discount=cust[C_DISCOUNT], w_tax=wh[W_TAX],
               d_tax=dist[D_TAX], o_ol_cnt=len(lines),
               o_entry_d=args["date"], lines=rows,
               total_amount=(total * scale + 50_000_000) // 100_000_000)
    return out


def payment(state, args):
    """Clause 2.5.2.2, the customer by id, on ``state``; its outputs."""
    w, d, tag, amount = args["w"], args["d"], args["tag"], args["amount"]
    c_w, c_d, c = args["c_w"], args["c_d"], args["c"]
    wh, dist = state[f"W/{w}"][-1], state[f"D/{w}/{d}"][-1]
    cust = state[f"C/{c_w}/{c_d}/{c}"][-1]
    balance = cust[C_BALANCE] - amount
    data = cust[C_DATA]
    if cust[C_CREDIT] == "BC":
        data = f"{c} {c_d} {c_w} {d} {w} {_money(amount)}|{data}"[:500]
    state[f"W/{w}"].append(_version(wh, tag, {W_YTD: wh[W_YTD] + amount}))
    state[f"D/{w}/{d}"].append(_version(dist, tag,
                                        {D_YTD: dist[D_YTD] + amount}))
    state[f"C/{c_w}/{c_d}/{c}"].append(_version(cust, tag, {
        C_BALANCE: balance, C_YTD_PAYMENT: cust[C_YTD_PAYMENT] + amount,
        C_PAYMENT_CNT: cust[C_PAYMENT_CNT] + 1, C_DATA: data}))
    state[f"H/{w}/{d}"].append((f"H/{w}/{d}", tag, c, c_d, c_w, d, w,
                                args["date"], amount,
                                wh[W_NAME] + "    " + dist[D_NAME]))
    out = {"w_id": w, "d_id": d, "c_id": c, "c_d_id": c_d, "c_w_id": c_w,
           "h_amount": amount, "h_date": args["date"],
           "w_address": list(wh[3:8]), "d_address": list(dist[3:8]),
           "c_first": cust[C_FIRST], "c_middle": cust[C_MIDDLE],
           "c_last": cust[C_LAST], "c_address": list(cust[5:10]),
           "c_phone": cust[C_PHONE], "c_since": cust[C_SINCE],
           "c_credit": cust[C_CREDIT], "c_credit_lim": cust[C_CREDIT_LIM],
           "c_discount": cust[C_DISCOUNT], "c_balance": balance}
    if cust[C_CREDIT] == "BC":
        out["c_data"] = data[:200]
    return out


TRANSACTIONS = {"new_order": new_order, "payment": payment}


def writes_anything(state, name, args):
    """Whether the txn writes: a New-Order naming an unused item rolls
    back."""
    return name != "new_order" or all(
        f"I/{i}" in state for i, _sw, _q in args["lines"])


def consistency(state, w=1):
    """Clause 3.3.2, conditions 1-4, on ``state``; a list of failures."""
    bad = []
    districts = sorted(k for k in state if k.startswith(f"D/{w}/"))
    ytd = sum(state[k][-1][D_YTD] for k in districts)
    if state[f"W/{w}"][-1][W_YTD] != ytd:
        bad.append(f"1: W_YTD {state[f'W/{w}'][-1][W_YTD]} != sum(D_YTD) "
                   f"{ytd}")
    for k in districts:
        d = k.rsplit("/", 1)[1]
        orders, o = [], 1
        while f"O/{w}/{d}/{o}" in state:       # the population's ORDERs
            orders.append(state[f"O/{w}/{d}/{o}"][-1])
            o += 1
        orders += state[f"O/{w}/{d}"]
        new = [e[O_ID] for e in state[f"NO/{w}/{d}"]]
        next_o = state[k][-1][D_NEXT_O_ID]
        if not next_o - 1 == max(e[O_ID] for e in orders) == max(new):
            bad.append(f"2: district {d}: D_NEXT_O_ID - 1 = {next_o - 1}, "
                       f"max(O_ID) {max(e[O_ID] for e in orders)}, "
                       f"max(NO_O_ID) {max(new)}")
        if max(new) - min(new) + 1 != len(new):
            bad.append(f"3: district {d}: NEW-ORDER holds {len(new)} rows "
                       f"for {min(new)}..{max(new)}")
        if sum(e[O_OL_CNT] for e in orders) != sum(len(e[O_LINES])
                                                   for e in orders):
            bad.append(f"4: district {d}: sum(O_OL_CNT) != ORDER-LINE rows")
    return bad


def as_json(x):
    """A value as JSON would carry it (tuples -> lists, keys -> str)."""
    return json.loads(json.dumps(x))


# -- the replay --------------------------------------------------------------

def version_chain(values):
    """A row's version order as tags; the population's run is one 0."""
    tags = [v[1] for v in values]
    at = 0
    while at < len(tags) and tags[at] == 0:
        at += 1
    return (0,) * bool(at) + tuple(tags[at:])


def replay(population, history, finals):
    """Hold a concurrent run to the reference.

    - ``population``: ``populate()``'s state, before any txn (not changed);
    - ``history``: one dict a txn attempt: ``name``, ``args`` (with its
      unique ``tag``), ``start`` and ``end`` (``end`` None: no answer came,
      it took effect whole or not at all), and for an answered one ``out``
      and ``seen`` (``{row_id: tag}`` of the version each read row held,
      None for no such row);
    - ``finals``: ``{row_id: tuple of values}`` read back at the end, for
      every row the run may have written, the WAREHOUSE, DISTRICT, ORDER
      and NEW-ORDER rows among them (the consistency conditions are read
      off these).

    Each row's final values are its version order; each txn's reads and
    writes become list-append ops over the tags (the population one txn
    before all), and ``serial_kv.replay`` builds the serial order (or names
    the cycle).  The two transactions then run in that order on a copy of
    the population: every answered txn's outputs, every row read back and
    the consistency conditions must come out as the run's.  Returns the
    order (indices into ``history``).  Raises ``NotTPCC``."""
    chains = {rid: version_chain(vals) for rid, vals in finals.items()}
    answered = [(-2, -1, {}, {rid: (0,) for rid, ch in chains.items()
                              if ch[:1] == (0,)})]
    unanswered, who = [], [None]
    for i, t in enumerate(history):
        read_rows, write_rows = rows_of(t["name"], t["args"])
        tag = t["args"]["tag"]
        appends = ({rid: (tag,) for rid in write_rows}
                   if writes_anything(population, t["name"], t["args"])
                   else {})
        if t["end"] is None:
            if appends:
                unanswered.append((t["start"], appends))
                who.append(("u", i))
            continue
        reads = {}
        for rid in read_rows:
            if rid not in chains:
                continue
            seen, chain = t["seen"].get(rid), chains[rid]
            if seen is None:
                reads[rid] = ()
            elif seen not in chain:
                raise NotTPCC("phantom", f"txn {tag} read version {seen} of "
                              f"{rid}, which the row never held")
            else:
                reads[rid] = chain[:chain.index(seen) + 1]
        answered.append((t["start"], t["end"], reads, appends))
        who.append(("a", i))
    try:
        order = serial_kv.replay(answered, unanswered, chains)
    except serial_kv.NotSerial as e:
        raise NotTPCC(e.kind, str(e)) from e
    n_answered = len(answered)
    index = [i for kind, i in who[1:] if kind == "a"]
    unanswered_index = [i for kind, i in who[1:] if kind == "u"]
    state = {rid: list(vals) for rid, vals in population.items()}
    done = []
    for k in order:
        if k == 0:
            continue                    # the population
        i = index[k - 1] if k < n_answered else \
            unanswered_index[k - n_answered]
        t = history[i]
        out = TRANSACTIONS[t["name"]](state, t["args"])
        done.append(i)
        if t["end"] is not None and as_json(out) != as_json(t["out"]):
            raise NotTPCC("result-mismatch", f"txn {t['args']['tag']} "
                          f"({t['name']}) answered {t['out']!r}; the "
                          f"reference computes {out!r}")
    for rid, vals in finals.items():
        if tuple(state.get(rid, ())) != tuple(vals):
            raise NotTPCC("row-mismatch", f"row {rid} reads back unlike the "
                          f"reference's {len(state.get(rid, ()))} values")
    bad = consistency({**population, **finals})
    if bad:
        raise NotTPCC("consistency", "; ".join(bad))
    return done
