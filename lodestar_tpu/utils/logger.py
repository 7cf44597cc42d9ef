"""Module-scoped loggers.

Reference: packages/utils/src/logger/winston.ts (winston with per-module
child loggers).  Here: stdlib logging with the same shape — a root
"lodestar" logger, ``get_logger(module)`` children, one-line timestamped
format, level from env LODESTAR_LOG_LEVEL.

Round-9 forensics additions:

- **Duplicate-handler guard**: handlers are tagged and re-configuration
  checks the live logger, not just the module-level ``_configured``
  flag.  ``logging.getLogger("lodestar")`` outlives this module's state
  (spawn children that re-import the package under a second sys.path
  entry, importlib.reload, test harnesses resetting ``_configured``) —
  before the guard each re-configure stacked another stderr handler and
  every line double-emitted.
- **JSON line mode**: ``set_format("json")`` / ``--log-format json`` /
  env ``LODESTAR_LOG_FORMAT=json`` switches the stderr handler to
  one-JSON-object-per-line output (machine-ingestable; the shape
  diagnostic bundles and log shippers want).
- **Batch-correlation injection**: every record is stamped with the
  merged-batch correlation id from the tracing ContextVar (``cid``),
  so a WARNING logged inside a pool flush lines up with that batch's
  spans and journal events.
- **Journal bridge**: WARNING+ records are mirrored into the forensics
  event journal (``forensics/journal.JournalHandler``) so the last
  errors before a crash survive in the black box even when stderr is
  truncated or lost.
"""

from __future__ import annotations

import json
import logging
import os
import sys
from typing import Optional

_ROOT_NAME = "lodestar"
_HANDLER_TAG = "_lodestar_role"  # marks handlers this module owns
_configured = False
_format = os.environ.get("LODESTAR_LOG_FORMAT", "text").lower()

TEXT_FORMAT = "%(asctime)s.%(msecs)03d %(levelname)-7s [%(name)s] %(message)s"
TEXT_DATEFMT = "%b-%d %H:%M:%S"


class _CidFilter(logging.Filter):
    """Stamp records with the current merged-batch correlation id (None
    outside a pool flush context)."""

    def filter(self, record: logging.LogRecord) -> bool:
        if not hasattr(record, "cid"):
            try:
                from ..tracing import current_batch_id

                record.cid = current_batch_id()
            except Exception:
                record.cid = None
        return True


class JsonFormatter(logging.Formatter):
    """One JSON object per line: ts (unix seconds), level, logger, msg,
    cid when in a batch context, exc on exceptions."""

    def format(self, record: logging.LogRecord) -> str:
        out = {
            "ts": round(record.created, 3),
            "level": record.levelname,
            "logger": record.name,
            "msg": record.getMessage(),
        }
        cid = getattr(record, "cid", None)
        if cid is not None:
            out["cid"] = cid
        if record.exc_info:
            out["exc"] = self.formatException(record.exc_info)
        return json.dumps(out, default=str)


def _make_formatter(fmt: str) -> logging.Formatter:
    if fmt == "json":
        return JsonFormatter()
    return logging.Formatter(fmt=TEXT_FORMAT, datefmt=TEXT_DATEFMT)


def _tagged_handler(root: logging.Logger, role: str) -> Optional[logging.Handler]:
    for h in root.handlers:
        if getattr(h, _HANDLER_TAG, None) == role:
            return h
    return None


def _configure_root(level: Optional[str] = None) -> logging.Logger:
    global _configured
    root = logging.getLogger(_ROOT_NAME)
    # guard on the LIVE logger: logging's registry survives a module
    # re-import (spawn children, reload), so `_configured` alone
    # would stack a second stderr handler and double-emit every line
    if not _tagged_handler(root, "stream"):
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(_make_formatter(_format))
        handler.addFilter(_CidFilter())
        setattr(handler, _HANDLER_TAG, "stream")
        root.addHandler(handler)
    if not _tagged_handler(root, "journal"):
        try:
            from ..forensics.journal import JournalHandler

            jh = JournalHandler()
            jh.addFilter(_CidFilter())
            setattr(jh, _HANDLER_TAG, "journal")
            root.addHandler(jh)
        except Exception:
            pass  # the journal must never be a reason logging fails
    if not _configured:
        root.propagate = False
        root.setLevel((level or os.environ.get("LODESTAR_LOG_LEVEL", "INFO")).upper())
        _configured = True
    return root


def get_logger(module: str = "", level: Optional[str] = None) -> logging.Logger:
    """Child logger named ``lodestar.<module>`` (winston childLogger analog)."""
    root = _configure_root(level)
    if not module:
        return root
    logger = root.getChild(module)
    if level:
        logger.setLevel(level.upper())
    return logger


def set_level(level: str) -> None:
    _configure_root().setLevel(level.upper())


def set_format(fmt: str) -> None:
    """Switch the stderr handler between ``text`` and ``json`` line
    output (CLI ``--log-format``)."""
    global _format
    fmt = fmt.lower()
    if fmt not in ("text", "json"):
        raise ValueError(f"log format must be 'text' or 'json', got {fmt!r}")
    _format = fmt
    handler = _tagged_handler(_configure_root(), "stream")
    if handler is not None:
        handler.setFormatter(_make_formatter(fmt))
