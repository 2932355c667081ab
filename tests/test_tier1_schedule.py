"""How tier-1 is handed out (``tests/conftest.py``, ROADMAP.md D9): the
order leads with the files known to be long (the rehearsals, the whole
steps' compiles, the kernels' compiles), pytest-xdist sends one test at a
time, a rehearsal file's tests take turns, and a worker that holds half
the memory mappings it may lets go of its loaded executables."""

import fcntl
import mmap
import os
import random
import subprocess
import sys
from types import SimpleNamespace

import conftest
import pytest

# One worker's collection order, cut to a few tests a file.
COLLECTED = [
    "tests/chipbench/test_chipbench.py::test_a_cell_is_one_line",
    "tests/chipbench/test_chipbench_mla_moe_hc.py::test_streams[2]",
    "tests/chipbench/test_chipbench_mla_moe_hc_rehearsal.py"
    "::test_the_cell_untraced_and_traced_on_the_cpu",
    "tests/chipbench/test_chipbench_rehearsal.py::test_resume",
    "tests/chipbench/test_chipbench_rehearsal.py::test_kill",
    "tests/test_agent.py::TestAgent::test_restart",
    "tests/test_rehearsal.py::test_a_name_alone_leads_nothing",
    "tests/test_tpu_compile.py::test_flash_kernel_compiles[bf16]",
    "tests/test_tpu_compile.py::test_flash_kernel_compiles[f32]",
    "tests/test_tpu_compile_axk2.py::test_axk2_step_fits_one_v5e",
    "tests/test_tpu_compile_xing4.py::test_xing4_step_fits_one_v5e",
    "tests/test_tpu_topology.py::test_another_directory/test_tpu_compile.py",
    "tests/test_zero.py::test_last",
]
REHEARSALS = [n for n in COLLECTED if "/chipbench/" in n and "_rehearsal.py::" in n]
STEPS = [n for n in COLLECTED if n.startswith("tests/test_tpu_compile_")]
KERNELS = [n for n in COLLECTED if n.startswith("tests/test_tpu_compile.py")]


def ordered(nodeids):
    """The node ids as ``pytest_collection_modifyitems`` leaves them."""
    items = [SimpleNamespace(nodeid=n) for n in nodeids]
    conftest.pytest_collection_modifyitems(items)
    return [item.nodeid for item in items]


def test_the_order_is_a_permutation_of_the_collection():
    assert sorted(ordered(COLLECTED)) == sorted(COLLECTED)


def test_the_rehearsals_lead_and_the_compiles_follow():
    long = REHEARSALS + STEPS + KERNELS
    assert len(REHEARSALS) == 3 and len(STEPS) == 2 and len(KERNELS) == 2
    assert ordered(COLLECTED)[: len(long)] == long


@pytest.mark.parametrize("rank", range(len(conftest.LONG_FIRST) + 1))
def test_each_group_keeps_the_order_it_was_collected_in(rank):
    group = [n for n in COLLECTED if conftest.long_first_rank(n) == rank]
    assert group, rank
    assert [n for n in ordered(COLLECTED) if n in group] == group


@pytest.mark.parametrize("seed", range(3))
def test_where_a_test_goes_is_its_node_id_alone(seed):
    """Two workers that collect the same list sort it alike, and a list
    in another order has the same groups in the same places: nothing
    but the id is read (no clock, no table of seconds, no hash)."""
    shuffled = list(COLLECTED)
    random.Random(seed).shuffle(shuffled)
    assert ordered(list(COLLECTED)) == ordered(COLLECTED)
    ranks = [conftest.long_first_rank(n) for n in ordered(shuffled)]
    assert ranks == [conftest.long_first_rank(n) for n in ordered(COLLECTED)]


@pytest.mark.parametrize(
    "nodeid, rank",
    [
        ("tests/chipbench/test_chipbench_sambay_rehearsal.py::test_x", 0),
        ("chipbench/test_chipbench_rehearsal.py::test_x", 0),
        ("tests/test_tpu_compile_axk2.py::test_x", 1),
        ("test_tpu_compile_keye.py::test_x[rehearsal.py]", 1),
        ("tests/test_tpu_compile.py::test_x", 2),
        ("tests/chipbench/test_chipbench.py::test_rehearsal.py", 3),
        ("tests/test_rehearsal.py::test_x", 3),
        ("tests/test_aot.py::test_tpu_compile.py", 3),
    ],
)
def test_a_file_name_decides_the_group(nodeid, rank):
    assert conftest.long_first_rank(nodeid) == rank


def test_the_collection_of_this_run_was_ordered(request):
    ranks = [conftest.long_first_rank(i.nodeid) for i in request.session.items]
    assert ranks == sorted(ranks)


@pytest.mark.parametrize(
    "given, left",
    [(None, 1), (1, 1), (8, 8), (0, 0)],
)
def test_one_test_at_a_time_unless_the_command_line_says(given, left):
    option = SimpleNamespace(maxschedchunk=given, dist="load")
    conftest.one_test_at_a_time(option)
    assert option.maxschedchunk == left


def test_nothing_is_set_where_xdist_is_not_loaded():
    option = SimpleNamespace(dist="no")
    conftest.one_test_at_a_time(option)
    assert vars(option) == {"dist": "no"}


def test_a_rehearsal_files_tests_take_turns(tmp_path):
    """Another worker's test of the same file waits while one runs."""
    shared = tmp_path / "test_chipbench_rehearsal.py"
    shared.write_text("")
    with open(shared) as other_worker:
        with conftest.its_turn(shared):
            with pytest.raises(BlockingIOError):
                fcntl.flock(other_worker, fcntl.LOCK_EX | fcntl.LOCK_NB)
        fcntl.flock(other_worker, fcntl.LOCK_EX | fcntl.LOCK_NB)


def test_the_mappings_of_this_process_are_counted():
    """One-page mappings whose protection alternates, so that the kernel
    merges none of them with its neighbour."""
    before = conftest.mappings()
    assert 0 < before < conftest.mappings_allowed()
    pages = [mmap.mmap(-1, mmap.PAGESIZE,
                       prot=mmap.PROT_READ | (mmap.PROT_WRITE * (i % 2)))
             for i in range(64)]
    assert conftest.mappings() >= before + 32
    for page in pages:
        page.close()


@pytest.mark.parametrize(
    "held, allowed, dropped",
    [(0, 65530, False), (32765, 65530, False), (32766, 65530, True),
     (60845, 65530, True), (600, 1000, True), (500, 1000, False)],
)
def test_executables_go_at_half_the_mappings_allowed(
        monkeypatch, held, allowed, dropped):
    import jax

    cleared = []
    monkeypatch.setattr(jax, "clear_caches", lambda: cleared.append(True))
    assert conftest.let_go_of_executables(held, allowed) is dropped
    assert cleared == [True] * dropped


LOADS_AND_LETS_GO = """
import conftest, jax, jax.numpy as jnp
def load(sizes):
    for n in sizes:
        jax.jit(lambda a: jnp.tanh(a @ a.T).sum())(jnp.ones((n, 3)))
load([2])  # what the backend maps at its first program stays
before = conftest.mappings()
load(range(3, 43))
loaded = conftest.mappings()
assert conftest.let_go_of_executables(loaded, 0)
print(before, loaded, conftest.mappings())
"""


def test_an_executable_let_go_gives_its_mappings_back():
    """What the guard counts on, in a process of its own (late in a
    whole run a worker's new mappings merge with their neighbours and
    the count says little): forty loaded executables hold mappings
    (3 to 18 each, jax 0.9.0) and ``jax.clear_caches`` returns them."""
    tests = os.path.dirname(os.path.abspath(__file__))
    done = subprocess.run(
        [sys.executable, "-c", LOADS_AND_LETS_GO], capture_output=True,
        text=True, cwd=os.path.dirname(tests),
        env={**os.environ, "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(tests), tests,
             os.environ.get("PYTHONPATH", "")])})
    assert done.returncode == 0, done.stderr[-2000:]
    before, loaded, left = map(int, done.stdout.split())
    assert loaded >= before + 40, (before, loaded, left)
    assert left <= before + (loaded - before) // 2, (before, loaded, left)
