"""``like`` and ``not like`` in the query language: the reference's
matcher against a regular-expression oracle, the validation of patterns,
the cardinalities and bytes a ``like`` records, TPC-H Q13 whole on a
table made by hand, and the program's mapping to the frontend."""

import re

import numpy as np
import pytest

from harness import program, queryspec, reference, roofline
from repro.core.plan.expr import Cmp, Expr, Lit

#: a small alphabet with the words TPC-H's patterns look for planted in it
TOKENS = ["a", "b", "ab", "ba", " ", "x", "special", "requests", "PROMO",
          "BRASS", "specialrequests"]


@pytest.fixture(scope="module")
def texts():
    rng = np.random.default_rng(2 ** 31 + 41)
    out = [b""]
    for _ in range(3000):
        n = int(rng.integers(0, 7))
        out.append("".join(TOKENS[i] for i in
                           rng.integers(0, len(TOKENS), n)).encode())
    return np.array(out)


def oracle(texts: np.ndarray, pattern: str) -> np.ndarray:
    rx = re.compile(b".*".join(re.escape(p.encode())
                               for p in pattern.split("%")), re.S)
    return np.array([rx.fullmatch(t) is not None for t in texts.tolist()])


@pytest.mark.parametrize("pattern", [
    "%", "%%", "", "PROMO%", "%BRASS", "ab%ba", "%special%requests%",
    "ab", "a%", "%a%b%", "%ab%ba%", "special%%requests", "%requests",
    "b%a%b", "%specialrequests%"])
def test_like_matches_the_oracle(pattern, texts):
    got = reference.like(texts, pattern)
    want = oracle(texts, pattern)
    assert got.dtype == bool and np.array_equal(got, want)
    if pattern not in ("%", "%%"):
        assert 0 < want.sum() < len(texts)


@pytest.mark.parametrize("text, pattern, want", [
    (b"aba", "ab%ba", False),       # the suffix may not reuse the prefix
    (b"abba", "ab%ba", True),
    (b"abXba", "ab%ba", True),
    (b"special requests", "%special%requests%", True),
    (b"requests special", "%special%requests%", False),
    (b"specialrequests", "%special%requests%", True),
    (b"Special requests", "%special%requests%", False),   # case-exact
    (b"", "", True),
    (b"", "%", True),
    (b"a", "", False),
])
def test_like_hand_cases(text, pattern, want):
    assert reference.like(np.array([text, b"zz"]), pattern)[0] == want


def test_like_refuses_a_number_column():
    with pytest.raises(ValueError, match="like on a int64 column"):
        reference.like(np.arange(3), "%1%")


@pytest.mark.parametrize("pred", [
    ["like", "c", "a_b"], ["not like", "c", "%a\\%%"],
    ["like", "c", 7], ["not like", "c", None], ["like", "c"],
    ["like", 3, "%a%"]],
    ids=["underscore", "escape", "int", "none", "no-pattern", "column"])
def test_validate_refuses_a_pattern(pred):
    with pytest.raises(ValueError):
        queryspec.validate({"scan": "t", "steps": [{"filter": pred}]})


@pytest.mark.parametrize("op", ["like", "not like"])
def test_like_stat_and_bytes(op):
    """A like records the rows it scanned, their text's bytes and the
    rows LIKE accepts (for ``not like`` too), and needs the text, its
    int64 offsets and a bool a row."""
    table = {"t": {"k": np.arange(4),
                   "c": np.array([b"PROMO box", b"big BRASS", b"PROMO",
                                  b"tin"])}}
    plan = {"scan": "t", "steps": [{"filter": [op, "c", "PROMO%"]}]}
    queryspec.validate(plan)
    rows, stats = reference.run(plan, table)
    assert sorted(r[0] for r in rows) == ([0, 2] if op == "like"
                                          else [1, 3])
    assert stats == [{"op": "like", "rows": 4, "value_bytes": 26,
                      "matches": 2}]
    assert roofline.like_bytes(stats[0]) == 26 + 5 * 8 + 4


#: TPC-H Q13 (clause 2.4.13) whole: customers left-joined to their
#: orders whose comment is not like '%special%requests%', the orders
#: counted per customer, the customers counted per order count
Q13_WHOLE = {"scan": "customer", "steps": [
    {"join": {"scan": "orders", "steps": [
        {"filter": ["not like", "o_comment", "%special%requests%"]}]},
     "on": "custkey", "how": "left"},
    {"group_by": ["custkey"], "aggs": {"c_count": ["orderkey", "count"]}},
    {"group_by": ["c_count"], "aggs": {"custdist": ["custkey", "count"]}}]}

Q13_HAND = {
    "customer": {"custkey": np.arange(1, 6, dtype=np.int64)},
    "orders": {
        "orderkey": np.array([1, 2, 3, 4, 5, 6, 7, 32], dtype=np.int64),
        "custkey": np.array([1, 1, 2, 2, 4, 4, 4, 5], dtype=np.int64),
        "o_comment": np.array([
            b"fluffily final deposits",
            b"special requests sleep",          # matches: left out
            b"carefully special pending requests",  # customer 2's only
            b"specialrequests",                     # orders both match
            b"requests special",                # wrong order: kept
            b"special",
            b"furiously regular requests",
            b"quiet packages"])}}


def test_q13_whole_on_hand_data():
    """Customer 2's only orders both match the pattern, so it joins to
    nothing and counts 0, beside customer 3, which has no orders."""
    queryspec.validate(Q13_WHOLE)
    got, stats = reference.run(Q13_WHOLE, Q13_HAND)
    assert sorted(got) == [(0, 2), (1, 2), (3, 1)]
    like, join = stats[0], stats[1]
    text = Q13_HAND["orders"]["o_comment"].tolist()
    assert like == {"op": "like", "rows": 8, "matches": 3,
                    "value_bytes": sum(map(len, text))}
    assert (join["op"], join["matches"], join["misses"]) == ("join", 5, 2)
    # the control's count counts each miss's null as a row
    control, _ = reference.run(Q13_WHOLE, Q13_HAND, control=True)
    assert sorted(control) == [(1, 4), (3, 1)]


def test_program_maps_like_to_the_frontend(monkeypatch):
    """``like`` becomes ``col(c).like(pattern)`` and ``not like`` its
    negation; the method stands in here for the program's own."""
    monkeypatch.setattr(Expr, "like", lambda self, pattern:
                        Cmp("==", self, Lit("LIKE " + pattern)),
                        raising=False)
    assert repr(program.predicate(["like", "o_comment", "%a%b%"])) == \
        "(col('o_comment') == lit('LIKE %a%b%'))"
    assert repr(program.predicate(["not like", "o_comment", "%a%"])) == \
        repr(~(Cmp("==", program.col("o_comment"), Lit("LIKE %a%"))))
