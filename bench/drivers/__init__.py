"""One loop per file; a traffic mix names its driver."""
