"""General generators: each runs every traffic mix that names it."""
