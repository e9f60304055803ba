"""Configuration builders and their plain references, by builder name."""
