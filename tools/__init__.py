"""Standalone operator/CI tools (lint, trace/metrics checkers, bundle
triage, firehose load harness).  A package so tests can
import the harness pieces (``tools.firehose``) in-process; every module
here remains directly runnable as a script."""
