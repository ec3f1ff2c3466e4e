"""Observability fixture: stats-dict key schemas (OB07). The
'phantom_stat' key has no policy_server_predicate_phantom_stat constant
in metrics_fix.py — seeded OB07 drift; 'covered_stat' does."""

OPTIMIZER_STAT_KEYS = (
    "covered_stat",
    "phantom_stat",
)
