"""Model configurations (``<name>.json``) and their plain references."""
