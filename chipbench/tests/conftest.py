"""Tiny sizes of the configurations added after ``test_chipbench_cells``
was written, handed to its ``TINY`` table before its ``tiny`` fixture
copies the benchmark: every cell then runs there end to end on the CPU
too."""

import pytest

TINY = {"bmi-appb-2p32": {"users": 1 << 16, "shard_users": 1 << 12,
                          "tenants": 16}}


@pytest.fixture(autouse=True, scope="module")
def _tiny_sizes_of_later_configs(request):
    table = getattr(request.module, "TINY", None)
    if isinstance(table, dict):
        for name, sizes in TINY.items():
            table.setdefault(name, sizes)
