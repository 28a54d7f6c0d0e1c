"""Data sources a traffic file names: ``pipeline`` and ``resident``."""
