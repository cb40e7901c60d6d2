"""Plain references, importing nothing of rocket_tpu."""
