"""Puts ``chipbench/`` and ``src/`` on the path for these checks."""

import common  # noqa: F401
