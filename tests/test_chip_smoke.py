"""CPU rehearsal of ``chip_smoke.py``: its one-chip phase and checks at a
tiny size, with the Pallas kernels in interpret mode on the main path."""

import chip_smoke
from repro.kernels import ops


def test_store_phase_passes_at_small_size(monkeypatch):
    # Steer the CPU run onto the kernel path the chip takes.
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    for fn in chip_smoke.MAIN_PATH_KERNELS.values():
        fn.clear_cache()  # variants must come from this phase alone
    lines = []
    failures = chip_smoke.run_store_phase(
        rows=20_000, tail_rows=2_000, batch_rows=2_000, seal_rows=4_096,
        nlist=64, nprobe=16, nq=16, seed=0, emit=lines.append,
    )
    assert failures == []
    searches = [ln for ln in lines if ln["phase"] == "search"]
    assert len(searches) == 8  # 4 query batches x 2 collections
    assert all(ln["deleted_returned"] == 0 for ln in searches)
    loads = {ln["collection"]: ln for ln in lines if ln["phase"] == "load"}
    assert all(ln["sealed_segments"] > 1 for ln in loads.values())
    (kernels,) = [ln for ln in lines if ln["phase"] == "kernels"]
    assert min(kernels["compiled_variants"].values()) >= 1


def test_main_refuses_a_host_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    assert '"ok"' not in capsys.readouterr().out
