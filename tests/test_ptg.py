"""PTG DSL tests: parser, compiler, execution, and the negative battery.

Models the reference's tests/dsl/ptg suite plus the ptgpp compile-error tests
(tests/dsl/ptg/ptgpp: JDFs that must fail at compile time).
"""

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.dsl.ptg import compiler as C
from parsec_tpu.dsl.ptg import parser as P
from parsec_tpu.dsl.ptg.compiler import compile_ptg


@pytest.fixture()
def ctx():
    c = Context(nb_cores=1)
    yield c
    c.fini()


CHAIN_SRC = """
// Ex04_ChainData-style chain: T(0) reads A(0), each T(k) passes X onward,
// the last task writes back to memory (BASELINE config 1)
%global NT
%global A

T(k)
  k = 0 .. NT-1
  : A(0, 0)
  RW X <- (k == 0) ? A(0, 0) : X T(k-1)
     -> (k < NT-1) ? X T(k+1) : A(0, 0)
BODY
  X = X + 1.0
END
"""


def test_parse_chain():
    prog = P.parse(CHAIN_SRC)
    assert [tc.name for tc in prog.task_classes] == ["T"]
    tc = prog.task_classes[0]
    assert tc.params == ["k"]
    assert tc.affinity.name == "A"
    assert len(tc.affinity.index_exprs) == 2
    assert len(tc.flows) == 1
    f = tc.flows[0]
    assert f.access == P.FLOW_RW
    assert [d.direction for d in f.deps] == ["in", "out"]
    assert f.deps[0].guard == "k == 0"
    assert f.deps[0].endpoint.kind == "memory"
    assert f.deps[0].else_endpoint.kind == "task"
    assert tc.bodies[0].device == "CPU"


def test_chain_executes(ctx):
    NT = 16
    A = TiledMatrix("A", 4, 4, 4, 4)
    A.fill(lambda m, n: np.zeros((4, 4), np.float32))
    prog = compile_ptg(CHAIN_SRC, "chain")
    tp = prog.instantiate(ctx, globals={"NT": NT},
                          collections={"A": A})
    ctx.add_taskpool(tp)
    ctx.wait()
    assert tp.completed
    # NT increments flowed through the chain and back to memory
    assert np.allclose(A.to_dense(), NT)


FORK_JOIN_SRC = """
%global W
%global A

SPLIT(z)
  z = 0 .. 0
  : A(0, 0)
  RW X <- A(0, 0)
     -> Y WORK(0 .. W-1)
BODY
  X = X * 1.0
END

WORK(i)
  i = 0 .. W-1
  : A(0, 0)
  RW Y <- X SPLIT(0)
     -> (i == 0) ? Y JOIN(0)
  CTL c -> (i > 0) ? c JOIN(0)
BODY
  Y = Y + i + 1
END

JOIN(z)
  z = 0 .. 0
  : A(0, 0)
  RW Y <- Y WORK(0)
     -> A(0, 0)
  CTL c <- c WORK(1 .. W-1)
BODY
  Y = Y * 2.0
END
"""


def test_fork_join_with_range_deps(ctx):
    """Broadcast out-dep (X -> Y WORK(0..W-1)) + CTL range gather
    (c <- c WORK(1..W-1)): JDF's multicast/join constructs."""
    W = 4
    A = TiledMatrix("A", 4, 4, 4, 4)
    A.fill(lambda m, n: np.full((4, 4), 5.0, np.float32))
    prog = compile_ptg(FORK_JOIN_SRC, "forkjoin")
    tp = prog.instantiate(ctx, globals={"W": W}, collections={"A": A})
    ctx.add_taskpool(tp)
    ctx.wait()
    assert tp.completed
    # JOIN doubles WORK(0)'s result: (5 + 0 + 1) * 2
    assert np.allclose(A.to_dense(), 12.0)


def test_range_gather_on_data_flow_rejected():
    """A data flow with a range gather input is a compile error (only CTL
    flows may gather; a data flow has exactly one input)."""
    src = """
%global A
T(k)
  k = 0 .. 3
  RW X <- A(k, 0)
     -> X U(0)

U(z)
  z = 0 .. 0
  RW X <- X T(0 .. 3)
     -> A(0, 0)
BODY
  X = X
END
"""
    # note: T lacks BODY too, but the range-gather check must fire on U
    src = src.replace("-> X U(0)\n", "-> X U(0)\nBODY\n  X = X\nEND\n")
    with pytest.raises(P.PTGSyntaxError):
        ctx = Context(nb_cores=1)
        try:
            compile_ptg(src).instantiate(ctx, globals={}, collections={"A": None})
        finally:
            ctx.fini()


GEMM_SRC = """
// Tiled GEMM as PTG (BASELINE config 2): C[m,n] += sum_k A[m,k]B[k,n]
%global MT
%global NT
%global KT
%global descA
%global descB
%global descC

GEMM(m, n, k)
  m = 0 .. MT-1
  n = 0 .. NT-1
  k = 0 .. KT-1
  : descC(m, n)
  priority = KT - k
  READ A <- descA(m, k)
  READ B <- descB(k, n)
  RW   C <- (k == 0) ? descC(m, n) : C GEMM(m, n, k-1)
       -> (k < KT-1) ? C GEMM(m, n, k+1) : descC(m, n)
BODY [type=TPU]
  C = C + jnp.dot(A, B, preferred_element_type=jnp.float32)
END
"""


def test_ptg_gemm(ctx):
    MT = NT = KT = 3
    TS = 16
    rng = np.random.default_rng(11)
    a = rng.standard_normal((MT*TS, KT*TS)).astype(np.float32)
    b = rng.standard_normal((KT*TS, NT*TS)).astype(np.float32)
    A = TiledMatrix("A", MT*TS, KT*TS, TS, TS)
    B = TiledMatrix("B", KT*TS, NT*TS, TS, TS)
    Cm = TiledMatrix("C", MT*TS, NT*TS, TS, TS)
    A.fill(lambda m, k: a[m*TS:(m+1)*TS, k*TS:(k+1)*TS])
    B.fill(lambda k, n: b[k*TS:(k+1)*TS, n*TS:(n+1)*TS])
    Cm.fill(lambda m, n: np.zeros((TS, TS), np.float32))
    prog = compile_ptg(GEMM_SRC, "gemm")
    tp = prog.instantiate(ctx, globals={"MT": MT, "NT": NT, "KT": KT},
                          collections={"descA": A, "descB": B, "descC": Cm})
    ctx.add_taskpool(tp)
    ctx.wait()
    np.testing.assert_allclose(Cm.to_dense(), a @ b, rtol=1e-3, atol=1e-3)


def test_two_classes_pipeline(ctx):
    """Producer/consumer across classes with a CTL dependency."""
    src = """
%global N
%global A

PROD(k)
  k = 0 .. N-1
  : A(k, 0)
  RW X <- A(k, 0)
     -> X CONS(k)
BODY
  X = X + 10.0
END

CONS(k)
  k = 0 .. N-1
  : A(k, 0)
  RW X <- X PROD(k)
     -> A(k, 0)
BODY
  X = X * 2.0
END
"""
    N = 4
    A = TiledMatrix("A", 4 * N, 4, 4, 4)
    A.fill(lambda m, n: np.full((4, 4), float(m), np.float32))
    prog = compile_ptg(src, "pipe")
    tp = prog.instantiate(ctx, globals={"N": N}, collections={"A": A})
    ctx.add_taskpool(tp)
    ctx.wait()
    for k in range(N):
        got = np.asarray(A.data_of(k, 0).newest_copy().payload)
        assert np.allclose(got, (k + 10.0) * 2.0), k


# ---------------------------------------------------------------------------
# negative battery (ref: tests/dsl/ptg/ptgpp — 17 must-fail JDFs)
# ---------------------------------------------------------------------------

NEGATIVE_SOURCES = {
    "no_body": """
%global A
T(k)
  k = 0 .. 3
  RW X <- A(k)
""",
    "param_without_range": """
%global A
T(k, m)
  k = 0 .. 3
  RW X <- A(k)
BODY
  X = X
END
""",
    "duplicate_params": """
%global A
T(k, k)
  k = 0 .. 3
  RW X <- A(k)
BODY
  X = X
END
""",
    "duplicate_flow": """
%global A
T(k)
  k = 0 .. 3
  RW X <- A(k)
  READ X <- A(k)
BODY
  X = X
END
""",
    "unknown_peer_class": """
%global A
T(k)
  k = 0 .. 3
  RW X <- A(k)
     -> X U(k+1)
BODY
  X = X
END
""",
    "unknown_peer_flow": """
%global A
T(k)
  k = 0 .. 3
  RW X <- A(k)
     -> Y T(k+1)
BODY
  X = X
END
""",
    "wrong_arity": """
%global A
T(k)
  k = 0 .. 3
  RW X <- A(k)
     -> X T(k+1, 0)
BODY
  X = X
END
""",
    "flow_without_input": """
%global A
T(k)
  k = 0 .. 3
  RW X -> A(k)
BODY
  X = X
END
""",
    "body_with_return": """
%global A
T(k)
  k = 0 .. 3
  RW X <- A(k)
BODY
  return X
END
""",
    "bad_expression": """
%global A
T(k)
  k = 0 .. )(
  RW X <- A(k)
BODY
  X = X
END
""",
    "too_many_flows": "%global A\nT(k)\n  k = 0 .. 3\n" + "".join(
        f"  READ F{i} <- A(k)\n" for i in range(20)) + "BODY\n  pass\nEND\n",
    "duplicate_class": """
%global A
T(k)
  k = 0 .. 3
  RW X <- A(k)
BODY
  X = X
END

T(m)
  m = 0 .. 3
  RW X <- A(m)
BODY
  X = X
END
""",
    "unknown_body_device": """
%global A
T(k)
  k = 0 .. 3
  RW X <- A(k)
BODY [type=FPGA]
  X = X
END
""",
    "body_without_end": """
%global A
T(k)
  k = 0 .. 3
  RW X <- A(k)
BODY
  X = X
""",
    "dep_outside_flow": """
%global A
T(k)
  k = 0 .. 3
  <- A(k)
BODY
  pass
END
""",
    "garbage_line": """
%global A
T(k)
  k = 0 .. 3
  this is not a valid construct !!!
  RW X <- A(k)
BODY
  X = X
END
""",
    "no_task_classes": """
%global A
""",
    # NULL / NEW are input-only (ref: ptgpp output_NULL*, output_NEW* —
    # "NULL data only supported in IN dependencies." / "Automatic data
    # allocation with NEW only supported in IN dependencies.")
    "output_NULL": """
%global A
T(k)
  k = 0 .. 3
  RW X <- A(k)
       -> NULL
BODY
  X = X
END
""",
    "output_NULL_true": """
%global A
T(k)
  k = 0 .. 10
  RW X <- A(k)
       -> (k < 5) ? NULL : A(k)
BODY
  X = X
END
""",
    "output_NULL_false": """
%global A
T(k)
  k = 0 .. 10
  RW X <- A(k)
       -> (k < 5) ? A(k) : NULL
BODY
  X = X
END
""",
    "output_NEW": """
%global A
T(k)
  k = 0 .. 3
  RW X <- A(k)
       -> NEW
BODY
  X = X
END
""",
    "output_NEW_true": """
%global A
T(k)
  k = 0 .. 10
  RW X <- A(k)
       -> (k < 5) ? NEW : A(k)
BODY
  X = X
END
""",
    "output_NEW_false": """
%global A
T(k)
  k = 0 .. 10
  RW X <- A(k)
       -> (k < 5) ? A(k) : NEW
BODY
  X = X
END
""",
}


@pytest.mark.parametrize("case", sorted(NEGATIVE_SOURCES))
def test_negative(case):
    src = NEGATIVE_SOURCES[case]
    with pytest.raises((P.PTGSyntaxError, SyntaxError)):
        prog = compile_ptg(src, case)
        # some cases only fail at class-build time
        ctx = Context(nb_cores=1)
        try:
            prog.instantiate(ctx, globals={}, collections={"A": None})
        finally:
            ctx.fini()


# --------------------------------------------------------------------------
# ranges are evaluated in declaration order, as a JDF's locals are
# --------------------------------------------------------------------------

def _tri_src(ranges):
    return f"""
%global NT
%global A
GEMM(m, n, k)
{ranges}
  : A(0, 0)
  CTL c <- (k > 0) ? c GEMM(m, n, k-1)
        -> (k < n-1) ? c GEMM(m, n, k+1)
BODY
  pass
END
"""


#: DPLASMA's potrf_zgemm(m, n, k): k first, m reads k, n reads both
DECLARED_KMN = _tri_src("  k = 0 .. NT-3\n  m = k+2 .. NT-1\n  n = k+1 .. m-1")
#: the same set, respelled so that declaration order is parameter order
DECLARED_MNK = _tri_src("  m = 2 .. NT-1\n  n = 1 .. m-1\n  k = 0 .. n-1")


def _space(src, nt, ctx):
    tp = compile_ptg(src, "tri").instantiate(
        ctx, globals={"NT": nt}, collections={"A": None})
    tc = tp._classes["GEMM"]
    return tp, tc, [tuple(loc[p] for p in ("m", "n", "k"))
                    for loc in tp._enum_class(tc)]


@pytest.mark.parametrize("nt", [3, 4, 7])
def test_ranges_in_declaration_order_enumerate_the_same_set(ctx, nt):
    """A bound may read any local declared above it, whatever the
    parameter order: k, m, n enumerates what m, n, k does, k outermost;
    task keys stay in parameter order."""
    tp, tc, kmn = _space(DECLARED_KMN, nt, ctx)
    _tp, _tc, mnk = _space(DECLARED_MNK, nt, ctx)
    want = {(m, n, k) for k in range(nt - 2) for m in range(k + 2, nt)
            for n in range(k + 1, m)}
    assert len(kmn) == len(want) == nt * (nt - 1) * (nt - 2) // 6
    assert set(kmn) == set(mnk) == want
    assert kmn == sorted(kmn, key=lambda t: (t[2], t[0], t[1]))
    assert mnk == sorted(mnk)
    assert tc.make_key(tp, {"k": 0, "m": 2, "n": 1}) == (2, 1, 0)
    assert tp._enum_class_fast(tc) is None      # the dict walk, not product


def test_a_bound_that_reads_a_later_local_is_a_syntax_error():
    """At compile time, not a NameError at instantiation."""
    src = _tri_src("  m = k+2 .. NT-1\n  k = 0 .. NT-3\n  n = k+1 .. m-1")
    with pytest.raises(P.PTGSyntaxError, match=r"range of 'm' reads \['k'\]"):
        compile_ptg(src, "tri")
    own = _tri_src("  k = 0 .. k\n  m = k+2 .. NT-1\n  n = k+1 .. m-1")
    with pytest.raises(P.PTGSyntaxError, match="range of 'k'"):
        compile_ptg(own, "tri")


def test_a_source_declared_in_parameter_order_enumerates_unchanged(ctx):
    """``GEMM_SRC`` (m, n, k over static bounds): the product order, and
    the fast enumerator still takes it."""
    tp = compile_ptg(GEMM_SRC, "gemm").instantiate(
        ctx, globals={"MT": 2, "NT": 3, "KT": 2},
        collections={"descA": None, "descB": None, "descC": None})
    tc = tp._classes["GEMM"]
    want = [(m, n, k) for m in range(2) for n in range(3) for k in range(2)]
    assert [tuple(loc[p] for p in ("m", "n", "k"))
            for loc in tp._enum_class(tc)] == want
    assert list(tp._enum_class_fast(tc)) == want


def test_descending_range(ctx):
    """Negative-step ranges include both endpoints (countdown chains)."""
    src = """
%global A
T(k)
  k = 3 .. 0 .. -1
  : A(0, 0)
  RW X <- (k == 3) ? A(0, 0) : X T(k+1)
     -> (k > 0) ? X T(k-1) : A(0, 0)
BODY
  X = X + 1.0
END
"""
    A = TiledMatrix("A", 4, 4, 4, 4)
    A.fill(lambda m, n: np.zeros((4, 4), np.float32))
    tp = compile_ptg(src, "down").instantiate(ctx, globals={}, collections={"A": A})
    ctx.add_taskpool(tp)
    ctx.wait()
    assert tp.completed
    assert np.allclose(A.to_dense(), 4.0)   # k = 3,2,1,0 all ran


# ---------------------------------------------------------------------------
# NULL forwarding, write_check, %prologue (ref: tests/dsl/ptg/ptgpp)
# ---------------------------------------------------------------------------

FORWARD_NULL_SRC = """
%global A
%global NB
Task(k)
  k = 0 .. NB
  : A(k, 0)
  {ACCESS} X <- (k == 0) ? NULL : X Task(k-1)
       -> (k < NB) ? X Task(k+1)
BODY
  pass
END
"""


@pytest.mark.parametrize("access", ["RW", "READ"])
def test_forward_null_fatals(ctx, access):
    """Forwarding a NULL on a data flow aborts with attribution at the
    source (ref: parsec.c:1879 'A NULL is forwarded';
    ptgpp forward_RW_NULL / forward_READ_NULL)."""
    NB = 3
    A = TiledMatrix("Afn" + access, 16, 4, 4, 4)
    A.fill(lambda m, n: np.ones((4, 4), np.float32))
    prog = compile_ptg(FORWARD_NULL_SRC.replace("{ACCESS}", access),
                       "fwdnull" + access)
    tp = prog.instantiate(ctx, globals={"NB": NB}, collections={"A": A})
    with pytest.raises(RuntimeError, match="A NULL is forwarded"):
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)


def test_forward_null_fatals_2rank():
    """The same NULL-forward abort fires on the source rank of a
    distributed chain (ref: forward_RW_NULL:mp)."""
    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.comm.threads import ThreadsCE, run_distributed
    from parsec_tpu.data.matrix import TwoDimBlockCyclic

    NB = 3

    def program(rank, fabric):
        ctx = Context(nb_cores=1, my_rank=rank, nb_ranks=2)
        RemoteDepEngine(ctx, ThreadsCE(fabric, rank))
        A = TwoDimBlockCyclic("Afn2", 16, 4, 4, 4, P=2, Q=1,
                              nodes=2, myrank=rank)
        A.fill(lambda m, n: np.ones((4, 4), np.float32))
        prog = compile_ptg(FORWARD_NULL_SRC.replace("{ACCESS}", "RW"),
                           "fwdnull2")
        tp = prog.instantiate(ctx, globals={"NB": NB}, collections={"A": A})
        try:
            ctx.add_taskpool(tp)
            ctx.wait(timeout=10)
            return "completed"
        except Exception as e:  # noqa: BLE001 - the fatal (rank 0) or the
            # starvation timeout it causes downstream (rank 1)
            return f"{type(e).__name__}: {e}"
        finally:
            try:
                ctx.fini(timeout=5)
            except Exception:
                pass

    results = run_distributed(2, program, timeout=60)
    # rank 0 owns Task(0) (the NULL source): the fatal fires there
    assert "A NULL is forwarded" in results[0]


WRITE_CHECK_SRC = """
%global A
%global NT
%global BLOCK

STARTUP(k)
  k = 0 .. NT
  : A(0, k)
  WRITE A1 -> A2 TASK1(k)
BODY
  A1 = (np.arange(BLOCK * BLOCK, dtype=np.float32) + k * BLOCK).reshape(BLOCK, BLOCK)
END

TASK1(k)
  k = 0 .. NT
  : A(0, k)
  WRITE A3 -> A1 TASK2(k)
  RW    A1 <- A(0, k)
           -> A2 TASK2(k)
  READ  A2 <- A1 STARTUP(k)
BODY
  A1 = A1 + 1.0
  A3 = A2
END

TASK2(k)
  k = 0 .. NT
  : A(0, k)
  READ A1 <- A3 TASK1(k)
  RW   A2 <- A1 TASK1(k)
          -> A(0, k)
BODY
  A2 = A2 + A1
END
"""


def _write_check_run(ctx, A, NT, BLOCK):
    prog = compile_ptg(WRITE_CHECK_SRC, "write_check")
    tp = prog.instantiate(ctx, globals={"NT": NT, "BLOCK": BLOCK},
                          collections={"A": A})
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    return tp


def test_write_check(ctx):
    """WRITE-only scratch flows forwarded through a 3-task pipeline: the
    final tile content proves every write propagated (ref: write_check.jdf
    — WRITE A1/A3 relay chains, RW chains, memory write-back)."""
    NT, BLOCK = 3, 4
    A = TiledMatrix("Awc", BLOCK, (NT + 1) * BLOCK, BLOCK, BLOCK)
    A.fill(lambda m, n: np.ones((BLOCK, BLOCK), np.float32))
    tp = _write_check_run(ctx, A, NT, BLOCK)
    assert tp.completed
    for k in range(NT + 1):
        # A(0,k) = (ones + 1) + startup_index = 2 + k*BLOCK + arange
        expect = (np.arange(BLOCK * BLOCK, dtype=np.float32) + k * BLOCK
                  ).reshape(BLOCK, BLOCK) + 2.0
        got = np.asarray(A.data_of(0, k).newest_copy().payload)
        np.testing.assert_allclose(got, expect)


def test_write_check_2rank():
    """write_check across 2 ranks (ref: write_check:mp): the WRITE relay
    and RW chains cross the wire via the remote-dep protocol."""
    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.comm.threads import ThreadsCE, run_distributed
    from parsec_tpu.data.matrix import TwoDimBlockCyclic

    NT, BLOCK = 3, 4

    def program(rank, fabric):
        ctx = Context(nb_cores=1, my_rank=rank, nb_ranks=2)
        RemoteDepEngine(ctx, ThreadsCE(fabric, rank))
        A = TwoDimBlockCyclic("Awc2", BLOCK, (NT + 1) * BLOCK, BLOCK, BLOCK,
                              P=1, Q=2, nodes=2, myrank=rank)
        A.fill(lambda m, n: np.ones((BLOCK, BLOCK), np.float32))
        _write_check_run(ctx, A, NT, BLOCK)
        out = {}
        for k in range(NT + 1):
            if A.rank_of(0, k) == rank:
                out[k] = np.asarray(A.data_of(0, k).newest_copy().payload)
        ctx.fini()
        return out

    results = run_distributed(2, program, timeout=90)
    seen = {}
    for out in results:
        seen.update(out)
    assert len(seen) == NT + 1
    for k, got in seen.items():
        expect = (np.arange(BLOCK * BLOCK, dtype=np.float32) + k * BLOCK
                  ).reshape(BLOCK, BLOCK) + 2.0
        np.testing.assert_allclose(got, expect)


PROLOGUE_SRC = """
%{
import math
NT = 7
def weight(k):
    return (k + 1) ** 0.5     # tracer-safe: bodies are jitted
def last(nt):
    return nt - int(math.copysign(1, nt))   # host-side helpers may use math
%}
%global A

T(k)
  k = 0 .. last(NT)
  : A(0, k)
  RW X <- A(0, k)
       -> A(0, k)
BODY
  X = X + weight(k)
END
"""


def test_prologue_block(ctx):
    """A %{...%} prologue carries helpers + constants the ranges and bodies
    use — the file is self-contained like a JDF with an inline-C prologue
    (ref: extern "C" %{...%} escapes, jdf2c.c:54)."""
    prog = compile_ptg(PROLOGUE_SRC, "prologue")
    assert "def weight" in prog.spec.prologue
    A = TiledMatrix("Apl", 4, 7 * 4, 4, 4)
    A.fill(lambda m, n: np.zeros((4, 4), np.float32))
    # no globals= needed: NT, weight, last all come from the prologue
    tp = prog.instantiate(ctx, globals={}, collections={"A": A})
    ctx.add_taskpool(tp)
    ctx.wait(timeout=30)
    assert tp.completed
    for k in range(7):
        np.testing.assert_allclose(
            np.asarray(A.data_of(0, k).newest_copy().payload),
            np.sqrt(k + 1), rtol=1e-6)


def test_prologue_unterminated_rejected():
    with pytest.raises(P.PTGSyntaxError, match="unterminated"):
        P.parse("%{\nx = 1\n")
