"""Round-trip property tests for the typed data plane's encodings.

For arbitrary nested Python values the three production representations
must agree exactly:

* ``serial.encode`` → ``serial.decode`` (the by-value wire format);
* ``containers.build_value`` → ``containers.to_python`` (the
  heap-resident pointer graph the CXL route passes by reference);
* ``containers.deep_copy`` across two DIFFERENT heaps (the §5.6
  ``copy_from`` structural traversal);

plus the ``ArgView`` surface (graph- and python-backed) and the
end-to-end ``invoke`` / ``invoke_serialized`` paths.

Drivers:

* a derandomized ``hypothesis`` strategy when the [test] extra is
  installed (CI installs it);
* a fixed + seeded-random corpus that ALWAYS runs (the pinned container
  image has no hypothesis).

Value domain = what both formats support: None, 64-bit signed ints,
finite floats, unicode strings, bytes, lists, string-keyed dicts.
(bools intentionally normalize to ints in both encodings and are
excluded from the agreement domain.)
"""

import math
import random

import pytest

from repro.core import Orchestrator, RPC, SharedHeap, serial
from repro.core import containers as C
from repro.core.marshal import ArgView
from repro.core.scope import create_scope

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pinned container image: corpus drivers only
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# the agreement check
# ---------------------------------------------------------------------------
def _assert_roundtrips(value):
    # serial: encode/decode
    assert serial.decode(serial.encode(value)) == value

    # containers: build in a heap, read back through the raw reader
    heap = SharedHeap(3, 256)
    scope = create_scope(heap, 64 * 4096)
    val = C.build_value(scope, value)
    assert C.to_python(heap, val) == value

    # deep_copy into a DIFFERENT heap agrees (§5.6 copy_from)
    heap2 = SharedHeap(4, 256)
    scope2 = create_scope(heap2, 64 * 4096)
    copied = C.deep_copy(heap, scope2, val)
    assert C.to_python(heap2, copied) == value

    # cross-representation agreement
    assert C.to_python(heap2, copied) == serial.decode(serial.encode(value))

    # the ArgView surface materializes identically over both backends
    gv = ArgView.graph(heap, val)
    pv = ArgView.python(value)
    assert gv.to_python() == value
    assert pv.to_python() == value


# normalize to the shared value domain (see module docstring)
_SCALARS = [
    None, 0, 1, -1, 2**63 - 1, -(2**63), 42,
    0.0, -0.5, 1.5e300, 5e-324, math.pi,
    "", "x", "κλειδί", "a" * 300, "\x00\x01", "🙂" * 40,
    b"", b"\x00\xff" * 17, b"raw bytes",
]


def _random_value(rng: random.Random, depth: int = 0):
    r = rng.random()
    if depth >= 3 or r < 0.45:
        return rng.choice(_SCALARS)
    if r < 0.7:
        return [_random_value(rng, depth + 1)
                for _ in range(rng.randrange(0, 6))]
    return {f"k{rng.randrange(100)}_{i}": _random_value(rng, depth + 1)
            for i in range(rng.randrange(0, 6))}


class TestRoundTripCorpus:
    """Always-run drivers (no hypothesis required)."""

    @pytest.mark.parametrize("value", _SCALARS)
    def test_scalars(self, value):
        _assert_roundtrips(value)

    def test_nested_fixtures(self):
        _assert_roundtrips({
            "user": "u42", "n": -7, "pi": math.pi,
            "media": [1, 2, [3, "four", None]],
            "meta": {"tags": ["a", "b"], "depth": {"x": [{"y": 0.25}]}},
            "empty_list": [], "empty_map": {},
        })
        _assert_roundtrips([[[[["deep"]]]], {"": [None, ""]}])

    def test_seeded_random_values(self):
        rng = random.Random(0xC001)
        for _ in range(150):
            _assert_roundtrips(_random_value(rng))

    def test_bool_normalizes_to_int_in_both(self):
        # both encodings deliberately flatten bools to i64 — they must at
        # least agree with each other
        assert serial.decode(serial.encode([True, False])) == [1, 0]
        heap = SharedHeap(3, 64)
        scope = create_scope(heap, 4096)
        assert C.to_python(heap, C.build_value(scope, [True, False])) \
            == [1, 0]


if HAVE_HYPOTHESIS:
    _keys = st.text(max_size=20)
    _values = st.recursive(
        st.none()
        | st.integers(min_value=-(2**63), max_value=2**63 - 1)
        | st.floats(allow_nan=False, allow_infinity=False)
        | st.text(max_size=60)
        | st.binary(max_size=60),
        lambda children: st.lists(children, max_size=5)
        | st.dictionaries(_keys, children, max_size=5),
        max_leaves=25,
    )

    class TestRoundTripHypothesis:
        @settings(derandomize=True, max_examples=120, deadline=None)
        @given(_values)
        def test_all_representations_agree(self, value):
            _assert_roundtrips(value)

        @settings(derandomize=True, max_examples=60, deadline=None)
        @given(st.dictionaries(_keys, _values, max_size=6))
        def test_map_point_lookup_agrees(self, doc):
            """map_get must return exactly dict semantics for every key
            (the length-filtered scan is an optimization, not a change
            of meaning)."""
            heap = SharedHeap(3, 256)
            scope = create_scope(heap, 64 * 4096)
            tag, root = C.build_value(scope, doc)
            if tag != C.T_MAP:
                return
            for k, v in doc.items():
                got = C.map_get(heap, root, k)
                assert got is not None
                assert C.to_python(heap, got) == v
            assert C.map_get(heap, root, "key-not-present-xyz") is None


# ---------------------------------------------------------------------------
# end-to-end: the two invoke routes return identical values
# ---------------------------------------------------------------------------
class TestInvokeAgreement:
    def test_pointer_and_serialized_routes_agree(self):
        orch = Orchestrator()
        ch = RPC(orch, pid=1).open("rt")

        def echo(ctx, args):
            v = args[0]   # scalars unwrap; containers come back as views
            return v.to_python() if isinstance(v, ArgView) else v

        ch.add_typed(9, echo)
        conn = RPC(orch, pid=2).connect("rt")
        rng = random.Random(7)
        for _ in range(25):
            v = _random_value(rng)
            # wrap so the echoed value is always vec-element 0
            p = conn.invoke(9, v, inline=True)
            s = conn.invoke_serialized(9, v, inline=True)
            norm = serial.decode(serial.encode(v))  # tuple→list etc.
            assert p == s == norm
