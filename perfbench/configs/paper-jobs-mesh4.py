"""paper-jobs-mesh4: the rows, the plain float64 reference and its bfloat16
control, which are ``paper-jobs``'s own (``paper-jobs.py``, loaded by
path), so that the job has one reference however it is deployed."""
from __future__ import annotations

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "perfbench_paper_jobs_reference", Path(__file__).with_name("paper-jobs.py"))
_ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ref)

make_rows = _ref.make_rows
answers = _ref.answers
gaps = _ref.gaps
bf16_rows = _ref.bf16_rows
control = _ref.control
