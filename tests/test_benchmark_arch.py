"""Tier-1 runs the benchmark's self-check ``benchmarks/tests/test_arch.py``
as it is, by import: its cases and fixtures are collected here. A case
fails when a program-side name the harness reads (``_decode_impl``, a
``ServingMetrics.summary()`` key, a span) is renamed — before the driver's
chip run would notice. In a file of its own: it is most of the self-check's
time, so ``--dist loadfile`` gives it a worker to itself.
"""

import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_arch")

from benchmarks.tests.test_arch import *  # noqa: E402,F401,F403
