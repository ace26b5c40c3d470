"""FCP reproduction package."""
